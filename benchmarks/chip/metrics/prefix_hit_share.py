"""Share of the prompt tokens admitted in the window that the KV cache
served from shared prefix pages (the pool's ``prefix_shared_tokens``
counter over the window, against the prompt lengths of the requests
admitted in it)."""


def read(obs):
    total = obs.get("prompt_tokens_admitted")
    if not total:
        return None
    return 100.0 * obs["prefix_shared_tokens"] / total
