"""Smoke run of the main path on a TPU, through the entry points a user calls.

  python chip_smoke.py             one chip: the llama3.2-1b trainer at its
                                   published widths, then the compressed wire
                                   path (CompressChunnel over the fabric)
  python chip_smoke.py --chips 4   four chips: every gradient transport against
                                   the xla reference, then a mid-run switch

The first line names JAX, the device and the compile-cache directory. The last
line is one JSON object, {"ok": true, "device": {...}}. The script exits
non-zero, without that line, when the first device is not a TPU or any phase
fails. Weights and data are random, made from fixed seeds. The times it prints
are smoke timings, not metrics.
"""
from __future__ import annotations

import argparse
import json
import math
import statistics
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

ARCH = "llama3.2-1b"
# One chip holds the full 16 layers with donated state at 2 x 256 tokens: the
# compile against a described v5e counts 14.96 GiB of its 15.75 GiB.
# Ten steps with the learning rate at its peak from step 3 (the launcher's
# CLI warms up over 10).
ONE_CHIP = dict(steps=10, seq=256, batch=2, warmup=3)
# Four chips: batch 4 splits over pod=2 x data=2. Depth is cut to 4 layers:
# ring, hierarchical and compressed_int8 flatten the whole gradient tree, and
# hierarchical keeps every parameter on every chip; at 8 layers hierarchical
# counts 15.86 GiB of 15.75 against a described v5e, at 4 layers 11.82.
FOUR_CHIP = dict(steps=6, seq=256, batch=4, warmup=3, layers=4)
SWITCH_AT = 3  # the ring -> compressed_int8 switch comes after this many steps
TRANSPORTS = ("psum", "ring", "hierarchical", "compressed_int8")
# Per-step loss within this relative gap of xla's, for every transport. On
# four v5e chips at 4 layers the gaps were 1.16e-4 to 1.61e-4 for the exact
# transports and 1.71e-4 for compressed_int8, so the int8 transport needs no
# looser bound; what tells it apart is the int8 all-gather in its step.
RTOL = 1e-3
BLOCK = 256
# (messages per batch, total bytes per batch): 1 KiB to 16 MiB, batch 1 and
# 64. 16 MiB is 16384 blocks of 256 floats, far past one 128-row tile.
WIRE_CASES = ((1, 1 << 10), (1, 1 << 16), (1, 1 << 20), (1, 1 << 24),
              (64, 1 << 10), (64, 1 << 20), (64, 1 << 24))


class SmokeFailure(RuntimeError):
    pass


def require(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def log(msg: str) -> None:
    print(msg, flush=True)


def _cut(cfg, published) -> str:
    if cfg.num_layers == published.num_layers:
        return f"full depth, {cfg.num_layers} layers"
    return f"depth cut {published.num_layers} -> {cfg.num_layers} layers"


def _timings(tag: str, step_times) -> None:
    steady = statistics.median(step_times[1:])
    log(f"{tag}: first step incl. compile {step_times[0]:.3f}s, compile "
        f"~{step_times[0] - steady:.3f}s, step after compile {steady:.4f}s "
        f"(smoke timings, not metrics)")


def _peak_bytes(device) -> int:
    stats = device.memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", -1))


def trainer_phase(start_kw: dict) -> None:
    """llama3.2-1b at its published widths on one chip, xla transport
    negotiated; the loss starts near ln(vocab), stays finite and falls."""
    import jax

    from repro.configs import get_config
    from repro.launch.train import train

    run = train(ARCH, transport="xla", mesh="none", **start_kw)
    cfg = run.cfg
    published = get_config(ARCH)
    log(f"trainer: {cfg.name} d_model={cfg.d_model} heads={cfg.num_heads}/"
        f"{cfg.num_kv_heads} hd={cfg.head_dim_} d_ff={cfg.d_ff} "
        f"vocab={cfg.vocab_size}; {_cut(cfg, published)}; "
        f"negotiated transport={run.trainer.transport_name}")
    for i, loss in enumerate(run.losses):
        log(f"trainer step {i} loss {loss:.5f}")
    _timings("trainer", run.step_times)
    log(f"trainer peak_bytes_in_use={_peak_bytes(jax.devices()[0])}")
    losses = run.losses
    require(run.trainer.transport_name == "xla", "negotiation did not pick xla")
    require((cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim_,
             cfg.d_ff, cfg.vocab_size)
            == (published.d_model, published.num_heads, published.num_kv_heads,
                published.head_dim_, published.d_ff, published.vocab_size),
            "a width differs from the published config")
    require(all(math.isfinite(l) for l in losses), f"non-finite loss {losses}")
    require(abs(losses[0] - math.log(cfg.vocab_size)) <= 0.5,
            f"step-0 loss {losses[0]} is not within 0.5 of ln(vocab)")
    require(losses[-1] < losses[0], f"loss did not fall: {losses}")


def _block_steps(x2d):
    import numpy as np

    amax = np.abs(x2d).max(axis=1)
    return np.where(amax > 0, amax / 127.0, 1.0).astype(np.float32)


def wire_phase() -> None:
    """CompressChunnel(use_kernel=True) over FabricTransport between two
    fabric endpoints; every decoded value within one quantization step of
    its input, the kernel's codes within 1 of the jnp oracle's, and the
    compiled programs holding the Pallas kernel."""
    import jax.numpy as jnp
    import numpy as np

    from repro.comm.wire import CompressChunnel, _fused_decode, _fused_encode
    from repro.core.fabric import Fabric
    from repro.core.runtime import FabricTransport

    fab = Fabric()
    a, b = fab.register("smoke-tx"), fab.register("smoke-rx")
    tx = CompressChunnel(block=BLOCK, use_kernel=True).connect_wrap(
        FabricTransport(a, "smoke-rx").connect_wrap(None))
    rx = CompressChunnel(block=BLOCK, use_kernel=True).connect_wrap(
        FabricTransport(b, "smoke-tx").connect_wrap(None))
    rng = np.random.default_rng(0)
    biggest = 0
    for batch, total in WIRE_CASES:
        per = total // 4 // batch
        msgs = [(rng.standard_normal(per) * 3.0).astype(np.float32)
                for _ in range(batch)]
        got = []
        t0 = time.perf_counter()
        tx.send(msgs)
        while len(got) < batch:
            buf = [None] * (batch - len(got))
            n = rx.recv(buf, timeout=60.0)
            require(n > 0, f"wire batch={batch} total={total}: nothing received")
            got += buf[:n]
        dt = time.perf_counter() - t0
        flat = np.concatenate(msgs)
        pad = (-flat.size) % BLOCK
        x2d = np.pad(flat, (0, pad)).reshape(-1, BLOCK)
        n_blocks = x2d.shape[0]
        biggest = max(biggest, n_blocks)
        y = np.pad(np.concatenate([np.asarray(m).reshape(-1) for m in got]),
                   (0, pad)).reshape(-1, BLOCK)
        err = np.abs(y - x2d) / _block_steps(x2d)[:, None]
        # kernel against the jnp oracle, on the device
        xd = jnp.asarray(x2d)
        pk = np.asarray(_fused_encode(xd, block=BLOCK, use_kernel=True))
        po = np.asarray(_fused_encode(xd, block=BLOCK, use_kernel=False))
        nq = n_blocks * BLOCK
        dcode = np.abs(pk[:nq].view(np.int8).astype(np.int32)
                       - po[:nq].view(np.int8).astype(np.int32)).max()
        dscale = np.abs(pk[nq:].view(np.float32) / po[nq:].view(np.float32) - 1).max()
        dk = np.asarray(_fused_decode(jnp.asarray(po), n_blocks=n_blocks,
                                      block=BLOCK, use_kernel=True))
        do = np.asarray(_fused_decode(jnp.asarray(po), n_blocks=n_blocks,
                                      block=BLOCK, use_kernel=False))
        ddec = np.abs(dk - do).max()
        enc = _fused_encode.lower(xd, block=BLOCK, use_kernel=True).compile()
        dec = _fused_decode.lower(jnp.asarray(pk), n_blocks=n_blocks, block=BLOCK,
                                  use_kernel=True).compile()
        kernel = ("tpu_custom_call" in enc.as_text()
                  and "tpu_custom_call" in dec.as_text())
        log(f"wire batch={batch} total={total}B n_blocks={n_blocks}: "
            f"max|err|/step={err.max():.4f} codes max|kernel-oracle|={dcode} "
            f"scales max rel={dscale:.2e} decode max|kernel-oracle|={ddec:.2e} "
            f"tpu_custom_call={kernel} send+recv {dt:.4f}s (smoke timing)")
        require(err.max() <= 1.0, "a decoded value is off by more than one step")
        require(dcode <= 1, "kernel codes differ from the oracle by more than 1")
        require(dscale <= 1e-6, "kernel scales differ from the oracle")
        require(ddec <= 1e-6 * np.abs(do).max(), "kernel decode differs from the oracle")
        require(kernel, "the compiled wire programs hold no Pallas kernel")
    require(biggest > 128, "no wire case spans more than one 128-row tile")


def _device_sets(tag: str, state) -> None:
    import jax

    flat = jax.tree_util.tree_flatten_with_path(state)[0]
    for path, leaf in flat:
        ids = sorted(d.id for d in leaf.sharding.device_set)
        log(f"{tag} {jax.tree_util.keystr(path)} {tuple(leaf.shape)} devices={ids}")
    spans = {len(leaf.sharding.device_set) for _, leaf in flat}
    require(spans == {jax.device_count()},
            f"{tag}: state leaves span {spans} devices, not all of them")


def _max_rel(got, ref) -> float:
    return max(abs(g - r) / abs(r) for g, r in zip(got, ref))


def _int8_on_the_wire(run) -> None:
    """The compiled compressed_int8 step all-gathers int8 codes across pods."""
    compiled = run.trainer.jitted.lower(run.state, run.batches(0)).compile()
    gathers = [l.strip() for l in compiled.as_text().splitlines()
               if " all-gather(" in l or " all-gather-start(" in l]
    s8 = [l for l in gathers if "= s8[" in l or "(s8[" in l]
    log(f"transport compressed_int8: {len(s8)} of {len(gathers)} all-gathers "
        f"in the compiled step carry s8: {[l.split(' = ')[0] for l in s8]}")
    require(s8, "the compressed_int8 step sends no int8 all-gather")


def transports_phase(start_kw: dict) -> None:
    """Every gradient transport on pod=2 x data=n/2 against the xla reference
    (same init, same batches), then one ring -> compressed_int8 switch mid-run
    that carries the state across."""
    from repro.configs import get_config
    from repro.launch.train import start, train

    steps = start_kw["steps"]
    losses = {}
    for t in ("xla",) + TRANSPORTS:
        run = train(ARCH, transport=t, mesh="pods", **start_kw)
        if t == "xla":
            log(f"transports: {run.cfg.name} d_model={run.cfg.d_model} "
                f"d_ff={run.cfg.d_ff} vocab={run.cfg.vocab_size}; "
                f"{_cut(run.cfg, get_config(ARCH))}")
        require(run.trainer.transport_name == t, f"negotiation did not pick {t}")
        losses[t] = run.losses
        log(f"transport {t}: losses {[round(l, 5) for l in run.losses]}")
        _timings(f"transport {t}", run.step_times)
        if t == "xla":
            _device_sets("xla state", run.state)
        if t == "compressed_int8":
            _int8_on_the_wire(run)
        del run
    ref = losses["xla"]
    require(all(math.isfinite(l) for l in ref), f"xla losses {ref}")
    for t, got in losses.items():
        rel = _max_rel(got, ref)
        log(f"transport {t}: max rel diff from xla {rel:.2e} (bound {RTOL:g})")
        require(len(got) == len(ref) and rel <= RTOL,
                f"{t} disagrees with xla: {got} vs {ref}")
    log(f"transport compressed_int8: max rel diff from psum "
        f"{_max_rel(losses['compressed_int8'], losses['psum']):.2e}")

    run = start(ARCH, transport="ring", mesh="pods", **start_kw)
    run.steps(SWITCH_AT)
    before = int(run.state.step)
    run.state = run.trainer.reconfigure(run.state, "compressed_int8")
    log(f"switch: {run.trainer.reconfig_log[-1]}")
    require(run.trainer.reconfig_log[-1].get("committed", False),
            "the ring -> compressed_int8 switch did not commit")
    require(run.trainer.transport_name == "compressed_int8", "switch target")
    require(int(run.state.step) == before, "the step counter did not carry over")
    run.steps(steps - SWITCH_AT)
    log(f"switch: losses {[round(l, 5) for l in run.losses]}")
    _device_sets("switched state", run.state)
    rel = _max_rel(run.losses, ref)
    log(f"switch: max rel diff from xla {rel:.2e} (bound {RTOL:g})")
    require(len(run.losses) == steps and rel <= RTOL,
            "the switched run lost the state it carried")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args()
    try:
        from repro.launch.cache import enable_compile_cache
    except ImportError as e:
        print(f"chip_smoke: the repository's src/ is not beside this script ({e})",
              file=sys.stderr)
        return 2
    import jax

    cache_dir = enable_compile_cache()
    devs = jax.devices()
    dev = devs[0]
    log(f"jax {jax.__version__} platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devs)} compile_cache={cache_dir}")
    if dev.platform != "tpu":
        print("chip_smoke: the first device is not a TPU", file=sys.stderr)
        return 1
    if args.chips == 4:
        require(len(devs) == 4, f"--chips 4 needs four devices, found {len(devs)}")
        transports_phase(FOUR_CHIP)
    else:
        trainer_phase(ONE_CHIP)
        wire_phase()
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
