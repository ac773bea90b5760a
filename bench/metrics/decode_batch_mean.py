"""Sequences per decode batch in the window (EngineStats deltas)."""


def read(run):
    b = run.stats["decode_batches"]
    return run.stats["decode_tokens"] / b if b else None
