"""Mean host time per batch in the `recv` calls that complete it (span
`bench.recv`): fabric receive, reassembly, the dequantize program and the
split into messages."""


def read(r):
    spans = r.spans.get("recv")
    return 1e3 * sum(spans) / len(spans) if spans else None
