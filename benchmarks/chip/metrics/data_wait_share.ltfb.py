"""Share of the training window the trainers spent waiting for their
datastore's prefetched batches (the change in ``Population``'s
``data_wait_seconds`` over the window, summed over trainers, which step
one after another)."""


def read(obs):
    if not obs.get("window_s") or "data_wait_s" not in obs:
        return None
    return 100.0 * obs["data_wait_s"] / obs["window_s"]
