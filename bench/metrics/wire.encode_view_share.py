"""Share of the wire path's batches whose floats went to the device with no
host copy: 100 x the count of the stage `wire.encode.views` over the counts
of `wire.encode.views` and `wire.encode.concat` (one of the two opens inside
`wire.encode.pack` each batch). None where the trace holds neither."""
from bench.stages import load


def read(r):
    view = load(r.out.trace_dir)
    if view is None:
        return None
    views, joined = view.count("wire.encode.views"), view.count("wire.encode.concat")
    if not views + joined:
        return None
    return 100.0 * views / (views + joined)
