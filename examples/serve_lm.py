"""Serve a small LM through the continuous-batching scheduler.

A mixed-length batch of prompts flows through the request queue: the
scheduler admits requests by token budget into a shared preallocated
KV-cache pool, interleaves prefill of new requests with batched decode
of in-flight ones, and frees slots per-request on completion.

Every model call runs through ONE DecodeSession (the family-agnostic
decode API): pass --spec-tokens K to decode speculatively — a drafter
proposes K tokens per round and the target verifies them in a single
multi-token session.step, with token-identical output (here the
drafter is the model itself, the accept-rate upper bound; in
production it is an earlier LTFB population checkpoint, see
`python -m repro.launch.serve --draft-ckpt`).

  PYTHONPATH=src python examples/serve_lm.py [--tokens 24]
  PYTHONPATH=src python examples/serve_lm.py --spec-tokens 4
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax

from repro.configs.registry import get_config
from repro.launch.serve import build_requests, parse_lens
from repro.models.lm import init_lm
from repro.serve.scheduler import Scheduler


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-0.6b")
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--slots", type=int, default=3)
    ap.add_argument("--prompt-lens", default="8,16,24")
    ap.add_argument("--tokens", type=int, default=24)
    ap.add_argument("--spec-tokens", type=int, default=0,
                    help="speculative decoding: draft tokens per round "
                         "(self-draft demo; 0 = off)")
    args = ap.parse_args()

    cfg = get_config(args.arch, smoke=True)
    print(f"arch={cfg.name} (reduced config, {cfg.param_count()/1e6:.1f}M "
          f"params), slots={args.slots}")
    params, _ = init_lm(cfg, jax.random.PRNGKey(0))

    lens = parse_lens(args.prompt_lens)
    max_len = max(lens) + args.tokens
    sched = Scheduler(cfg, params, num_slots=args.slots, max_len=max_len,
                      draft_params=params if args.spec_tokens > 0 else None,
                      spec_tokens=args.spec_tokens)
    for r in build_requests(cfg, args.requests, lens, args.tokens, seed=1):
        sched.submit(r)
    results = sched.run()
    sched.stats.report()
    print("sample continuation (token ids):",
          list(map(int, results[0][:12])))


if __name__ == "__main__":
    main()
