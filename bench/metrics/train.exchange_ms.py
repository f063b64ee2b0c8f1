"""Mean device time per step of the compressed transport's exchange: the
ops under `transport/exchange` (the all-gathers of int8 codes and scales
over `pod`; an asynchronous one shows as its start and done events), the
union of their intervals on each chip, averaged over the cell's chips."""
from bench.transport import per_step_ms


def read(r):
    return per_step_ms(r, ("transport", "exchange"))
