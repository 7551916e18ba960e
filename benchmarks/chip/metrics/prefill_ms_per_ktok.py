"""Prefill: host time of ``SlotEngine.prefill`` (``ServingEngine``'s
prefill, jitted per prompt-length bucket, and its write into the KV
stores), ended by a wait for the stores, per 1000 prompt tokens of the
counted requests, in ms."""


def read(run):
    done = [r for r in run.requests if r.prefill_end is not None]
    tokens = sum(len(r.prompt) for r in done)
    if not tokens:
        return None
    return 1e6 * sum(r.prefill_end - r.prefill_start for r in done) / tokens
