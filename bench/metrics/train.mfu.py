"""Model FLOP utilisation of the whole step: model FLOPs per token
(`bench.flops.model_flops_per_token`) times the traced run's tokens per
second, over chips times the bf16 peak of the device kind."""


def read(r):
    peak = r.facts.get("peak")
    tps = r.facts.get("tokens_per_s")
    if not peak or not tps:
        return None
    return 100.0 * r.facts["flops_per_token"] * tps / (len(r.devices) * peak["bf16_flops"])
