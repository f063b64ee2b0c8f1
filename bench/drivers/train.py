"""Training cells: the ReconfigurableTrainer, built and negotiated as
`repro.launch.train.start` builds it, driven through `trainer.run`.

Set-up makes the state on the device from the seed in one jitted call (the
benchmark's weights, `bench.weights`), takes the first three steps through
the window's own call and feed, and reads from the state what the reference
is compared with: the first gradient from Adam's first moment after step 1,
and the parameters' change after step 3. The window then carries the same
trainer and state on, one step after another, for `--seconds`. After it the
state is freed and the plain reference (`bench.reference.lm`) takes the same
three steps on the same batches from the same seed.

Configuration keys (besides the model's own): `train.mesh` (a mesh of
`repro.launch.train.MESHES`; "none" is one chip), `train.transport`, and
`optimizer` (the TrainConfig as run).
"""
from __future__ import annotations

import dataclasses
import gc
import math
import sys
import time
from typing import Dict

import jax
import jax.numpy as jnp

from bench import compare, flops, weights
from bench.harness import Check, Outcome, Run, import_generator
from bench.peaks import peaks
from bench.reference import lm as ref_lm

SETUP_STEPS = 3


def model_config(cfg: dict):
    from repro.configs.base import ModelConfig

    return ModelConfig(
        name=cfg["name"], family="dense",
        num_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        num_heads=cfg["num_attention_heads"], num_kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg.get("head_dim") or cfg["hidden_size"] // cfg["num_attention_heads"],
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        qkv_bias=cfg.get("qkv_bias", False), rope_theta=cfg["rope_theta"],
        norm_eps=cfg["rms_norm_eps"], tie_embeddings=cfg["tie_word_embeddings"],
        max_position_embeddings=cfg["max_position_embeddings"])


def train_config(opt: dict):
    from repro.configs.base import TrainConfig

    names = {f.name for f in dataclasses.fields(TrainConfig)}
    return TrainConfig(**{k: v for k, v in opt.items() if k in names})


def flat_paths(tree) -> Dict[str, object]:
    leaves = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {"/".join(str(k.key) for k in path): leaf for path, leaf in leaves}


def build_trainer(cfg: dict, traffic: dict):
    """The trainer as `repro.launch.train.start` builds it."""
    from repro import compat
    from repro.configs.base import ShapeConfig
    from repro.launch.train import MESHES
    from repro.train.trainer import HostSpec, ReconfigurableTrainer

    t = cfg["train"]["transport"]
    shape = ShapeConfig("bench", int(traffic["seq_len"]), int(traffic["batch"]), "train")
    mesh = compat.set_mesh(MESHES[cfg["train"]["mesh"]]())
    trainer = ReconfigurableTrainer(
        model_config(cfg), shape, mesh, tcfg=train_config(cfg["optimizer"]),
        transport=t, hosts=[HostSpec(0, [t, "xla"])])
    if trainer.transport_name != t:
        raise RuntimeError(f"negotiation gave {trainer.transport_name}, not {t}")
    return trainer


def make_state(trainer, lay, seed: int):
    """The whole train state on the device in one jitted call: the seed's
    weights, zero moments and zero transport state."""
    from repro.comm.chunnels import init_grad_states
    from repro.optim.adamw import AdamWState
    from repro.train.step import TrainState

    shapes = trainer.model.param_shapes()
    held = {p: tuple(s.shape) for p, s in flat_paths(shapes).items()}
    if held != dict(lay):
        raise RuntimeError(f"the program's parameters differ from the reference "
                           f"layout: {sorted(set(held.items()) ^ set(lay))}")
    treedef = jax.tree_util.tree_structure(shapes)
    paths = list(flat_paths(shapes))
    opt_dtype = jnp.dtype(trainer.tcfg.opt_dtype)
    comm_shapes = init_grad_states(trainer.chunnels, shapes)

    def make(key):
        params = treedef.unflatten(
            [weights.make_leaf(key, p, held[p]) for p in paths])
        zeros = lambda: jax.tree.map(lambda s: jnp.zeros(s.shape, opt_dtype), shapes)
        comm = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), comm_shapes,
                            is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))
        return TrainState(params, AdamWState(zeros(), zeros(), jnp.zeros((), jnp.int32)),
                          comm, jnp.zeros((), jnp.int32))

    return jax.jit(make, out_shardings=trainer.state_sh)(weights.base_key(seed))


def peak_bytes(devices) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0)) for d in devices)


def compare_to_reference(cfg: dict, got: dict, ref: dict) -> Dict[str, float]:
    """The four numbers: loss gap (nats, worst step), pre-clip gradient norm
    gap (relative, worst step), first clipped gradient (worst leaf) and the
    parameters' change after three steps (worst leaf that moves)."""
    loss = max(abs(a - b) for a, b in zip(got["losses"], ref["losses"]))
    gnorm = max(abs(a - b) / b for a, b in zip(got["grad_norms"], ref["grad_norms"]))
    grad, _ = compare.worst_gap(got["grad1"], ref["grad1"])
    change, _ = compare.worst_gap(got["change"], ref["change"],
                                  compare.moving_leaves(ref["grad1"]))
    if any(not math.isfinite(x) for x in got["losses"]):
        loss = float("inf")
    return {"loss_gap": loss, "grad_norm_gap": gnorm, "grad_gap": grad,
            "change_gap": change}


CONTROLS = {"fp8": dict(precision="fp8")}


def run_controls(run: Run, cfg: dict, stream) -> Outcome:
    """Readings of the control, the reference one precision down put in the
    program's place; no window."""
    t = time.perf_counter()
    names = run.control.split(",")
    unknown = [n for n in names if n not in CONTROLS]
    if unknown:
        raise KeyError(f"no control {unknown}; known: {sorted(CONTROLS)}")
    batches = [stream.batch(i) for i in range(SETUP_STEPS)]
    ref = ref_lm.run_reference(cfg, cfg["optimizer"], run.seed, batches,
                               device=run.devices[0])
    checks = []
    for name in names:
        got = ref_lm.run_reference(cfg, cfg["optimizer"], run.seed, batches,
                                   device=run.devices[0], **CONTROLS[name])
        limits = run.cell.checks["limits"]
        for k, v in compare_to_reference(cfg, got, ref).items():
            checks.append(Check(f"{name}.{k}", v, limits.get(k, float("inf"))))
    nan = float("nan")
    return Outcome(setup_s=time.perf_counter() - t,
                   metrics={m["name"]: nan for m in run.cell.end_to_end},
                   attempted=SETUP_STEPS, failed=0, checks=checks,
                   memory_peak_bytes=peak_bytes(run.devices), window_s=0.0)


def first_steps(trainer, state, feed, cfg: dict, lay, seed: int):
    """The first steps through `trainer.run` with the window's feed, and
    what is read from them for the reference: each step's loss and pre-clip
    gradient norm, the first clipped gradient's norms per leaf from Adam's
    first moment (m = (1 - beta1) g after one step), and the parameters'
    change per leaf after the last."""
    vocab = cfg["vocab_size"]
    b1 = cfg["optimizer"]["beta1"]
    got = {"losses": [], "grad_norms": []}
    for i in range(SETUP_STEPS):
        state, hist = trainer.run(state, feed, 1)
        got["losses"].append(hist[0]["loss"])
        got["grad_norms"].append(hist[0]["grad_norm"])
        if i == 0:
            got["grad1"] = compare.leaf_norms(
                flat_paths(state.opt.m), vocab, scale=1.0 / (1.0 - b1),
                stacked=ref_lm.stacked(lay))
    got["change"] = ref_lm.change_norms(flat_paths(state.params), seed, lay, vocab)
    return state, got


def run(run: Run) -> Outcome:
    cell = run.cell
    cfg, traffic = cell.config, cell.traffic
    stream = import_generator(traffic["generator"]).make(traffic, run.seed, cfg)
    if run.control:
        return run_controls(run, cfg, stream)
    lay = ref_lm.layout(cfg)

    def feed(step: int) -> dict:
        with run.span("input"):
            return stream.batch(step)

    trainer = build_trainer(cfg, traffic)
    state = make_state(trainer, lay, run.seed)
    state, got = first_steps(trainer, state, feed, cfg, lay, run.seed)
    setup_s = time.perf_counter() - run.t0

    steps = failed = 0
    with run.window():
        t_start = time.perf_counter()
        while True:
            with run.span("step"):
                state, hist = trainer.run(state, feed, 1)
            steps += 1
            failed += not math.isfinite(hist[0]["loss"])
            t_end = time.perf_counter()
            if t_end - t_start >= run.seconds:
                break
    window_s = t_end - t_start
    step_s = sorted(run.spans["step"])
    print(f"bench: {steps} steps, step s min {step_s[0]:.4f} median "
          f"{step_s[len(step_s) // 2]:.4f} max {step_s[-1]:.4f}", file=sys.stderr)
    tokens_per_s = steps * stream.tokens_per_step / window_s
    memory = peak_bytes(run.devices)
    del state, trainer
    gc.collect()

    t_ref = time.perf_counter()
    ref = ref_lm.run_reference(cfg, cfg["optimizer"], run.seed,
                               [stream.batch(i) for i in range(SETUP_STEPS)],
                               device=run.devices[0])
    print(f"bench: reference took {time.perf_counter() - t_ref:.1f} s, "
          f"window {window_s:.1f} s", file=sys.stderr)
    gaps = compare_to_reference(cfg, got, ref)
    limits = cell.checks["limits"]
    for k, v in gaps.items():  # the numbers with no limit are read, not compared
        if k not in limits:
            print(f"bench: reading {k} = {v!r} (not compared)", file=sys.stderr)
    checks = [Check(k, v, limits[k]) for k, v in gaps.items() if k in limits]
    return Outcome(
        setup_s=setup_s, metrics={"train_tokens_per_s": tokens_per_s},
        attempted=steps, failed=failed, checks=checks, memory_peak_bytes=memory,
        window_s=window_s, trace_dir=run.trace_dir,
        facts={"tokens_per_s": tokens_per_s, "steps": steps,
               "flops_per_token": flops.model_flops_per_token(cfg, int(traffic["seq_len"])),
               "peak": peaks(run.devices[0].device_kind) if run.devices[0].platform == "tpu" else None})
