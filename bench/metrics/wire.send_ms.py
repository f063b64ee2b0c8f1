"""Mean host time per batch in `send` (span `bench.send`): concat, the
quantize program, the copy back, framing and fabric delivery."""


def read(r):
    spans = r.spans.get("send")
    return 1e3 * sum(spans) / len(spans) if spans else None
