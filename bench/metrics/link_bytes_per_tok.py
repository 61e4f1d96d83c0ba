"""LMB tier (``core/buffer.py``, ``core/offload.py``): bytes the KV
store's ``TierExecutor`` moved between ``pinned_host`` and HBM in the
window, per output token of the window."""


def read(run):
    if run.out_tokens == 0:
        return None
    return run.link_bytes / run.out_tokens
