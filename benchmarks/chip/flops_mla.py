"""Operations and bytes of the latent (MLA) paged decode kernel, from
shapes alone (``kernels/latent_attention.py``).

Per row and layer, one query of ``num_attention_heads`` heads over the
row's ``length`` cached tokens in the absorbed form: scores over the
whole cached row (``kv_lora_rank + qk_rope_head_dim`` values) and the
softmax-weighted ``c_kv`` (``kv_lora_rank`` values), 2 operations a
multiply-add.  Bytes: every page the row touches, whole, at the row's
published width in bf16 (576 x 2 B a token; the 64 lanes of padding the
pool's rows carry are overhead, not need), the float32 query and the
float32 output.
"""
from __future__ import annotations

from typing import Dict, Iterable


def latent_attention_cost(cfg: dict, lengths: Iterable[int],
                          block_size: int) -> Dict[str, float]:
    """FLOPs and bytes of one call of the kernel (one layer) over rows
    attending to ``lengths`` tokens each."""
    H = cfg["num_attention_heads"]
    rank = cfg["kv_lora_rank"]
    width = rank + cfg["qk_rope_head_dim"]
    flops = nbytes = 0.0
    for n in lengths:
        pages = -(-int(n) // block_size)
        flops += 2.0 * H * n * (width + rank)
        nbytes += pages * block_size * width * 2 + H * width * 4 \
            + H * rank * 4
    return {"flops": flops, "bytes": nbytes}
