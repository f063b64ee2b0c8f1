"""Mean host time per step in the benchmark's batch callable, which the
trainer calls at the top of every step (span `bench.input`)."""


def read(r):
    spans = r.spans.get("input")
    return 1e3 * sum(spans) / len(spans) if spans else None
