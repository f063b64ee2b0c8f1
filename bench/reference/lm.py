"""Plain reference for the training cells: a dense decoder as Qwen2 and
Mistral-NeMo publish it, in float32 at `HIGHEST` matmul precision, with its
loss, gradients and AdamW, three steps from the seed's weights.

Per layer: x += Wo . attn(RoPE(Wq h + bq), RoPE(Wk h + bk), Wv h + bv) with
h = RMSNorm(x), causal softmax over grouped KV heads (query head i reads KV
head i // (H / KH)); then x += Wdown (silu(Wgate h) * Wup h) with
h = RMSNorm(x). RoPE rotates the two halves of each head (the "rotate_half"
form) at frequencies theta^(-2i/hd). The loss is the mean next-token
cross-entropy over every position of every row, over the real vocabulary.

AdamW as the configuration states it: gradients clipped to a global norm,
decoupled weight decay on every parameter, a linear warm-up and a cosine
schedule, moments in float32.

It fits one chip by computing a batch row by row and attention one KV group
at a time, each under rematerialisation, and by keeping Adam's moments on the
host, one leaf on the device at a time. None of that changes the arithmetic.

`precision="fp8"` rounds both operands of every matrix product to float8
(e4m3, scaled per tensor by its largest magnitude) on the way forward, with
the gradient passed straight through: the control, one precision below the
bfloat16 the configuration computes in.
"""
from __future__ import annotations

import functools
import math
from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from bench import compare, weights

HIGHEST = jax.lax.Precision.HIGHEST
F8_MAX = 448.0


def _fp8(x):
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / F8_MAX
    q = (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
    return x + jax.lax.stop_gradient(q - x)


def _ops(precision: str):
    rnd = _fp8 if precision == "fp8" else (lambda x: x)

    def mm(a, b):
        return jnp.matmul(rnd(a), rnd(b), precision=HIGHEST)

    def ein(spec, a, b):
        return jnp.einsum(spec, rnd(a), rnd(b), precision=HIGHEST)

    return mm, ein


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """x: (S, heads, hd)."""
    s, hd = x.shape[0], x.shape[-1]
    freqs = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


class Reference:
    def __init__(self, cfg: dict, precision: str = "f32"):
        self.cfg = cfg
        self.d = cfg["hidden_size"]
        self.L = cfg["num_hidden_layers"]
        self.H, self.KH = cfg["num_attention_heads"], cfg["num_key_value_heads"]
        self.hd = cfg.get("head_dim") or self.d // self.H
        self.V = cfg["vocab_size"]
        self.eps = cfg["rms_norm_eps"]
        self.theta = cfg["rope_theta"]
        self.bias = cfg.get("qkv_bias", False)
        self.mm, self.ein = _ops(precision)
        self.grad_fn = jax.jit(jax.value_and_grad(self.batch_loss))

    # -- forward ---------------------------------------------------------------
    def attention(self, q, k, v):
        s, G = q.shape[0], self.H // self.KH
        qg = q.reshape(s, self.KH, G, self.hd).transpose(1, 0, 2, 3)
        kg, vg = k.transpose(1, 0, 2), v.transpose(1, 0, 2)
        causal = jnp.tril(jnp.ones((s, s), bool))

        @jax.checkpoint
        def group(args):
            qq, kk, vv = args  # (S, G, hd), (S, hd), (S, hd)
            sc = self.ein("qgd,kd->gqk", qq, kk) / math.sqrt(self.hd)
            sc = jnp.where(causal[None], sc, -jnp.inf)
            return self.ein("gqk,kd->qgd", jax.nn.softmax(sc, axis=-1), vv)

        o = jax.lax.map(group, (qg, kg, vg))  # (KH, S, G, hd)
        return o.transpose(1, 0, 2, 3).reshape(s, self.H * self.hd)

    def layer(self, p, x):
        s, mm = x.shape[0], self.mm
        h = _rms(x, p["ln1/scale"], self.eps)
        q, k, v = mm(h, p["attn/wq/w"]), mm(h, p["attn/wk/w"]), mm(h, p["attn/wv/w"])
        if self.bias:
            q, k, v = q + p["attn/wq/b"], k + p["attn/wk/b"], v + p["attn/wv/b"]
        q = _rope(q.reshape(s, self.H, self.hd), self.theta)
        k = _rope(k.reshape(s, self.KH, self.hd), self.theta)
        v = v.reshape(s, self.KH, self.hd)
        x = x + mm(self.attention(q, k, v), p["attn/wo/w"])
        h = _rms(x, p["ln2/scale"], self.eps)
        return x + mm(jax.nn.silu(mm(h, p["mlp/gate/w"])) * mm(h, p["mlp/up/w"]),
                      p["mlp/down/w"])

    def row_loss(self, params, tokens, labels):
        """Summed next-token loss of one row."""
        x = params["embed/table"][:self.V][tokens]
        layer = jax.checkpoint(self.layer)
        for i in range(self.L):
            x = layer({k[len("layers/"):]: v[i] for k, v in params.items()
                       if k.startswith("layers/")}, x)
        h = _rms(x, params["final_norm/scale"], self.eps)
        logits = self.mm(h, params["lm_head/w"][:, :self.V])
        gold = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
        return jnp.sum(jax.nn.logsumexp(logits, axis=-1) - gold)

    def batch_loss(self, params, tokens, labels):
        def body(acc, xs):
            return acc + self.row_loss(params, *xs), None

        total, _ = jax.lax.scan(jax.checkpoint(body), jnp.zeros((), jnp.float32),
                                (tokens, labels))
        return total / tokens.size


@functools.partial(jax.jit, donate_argnums=(0,))
def _adam_leaf(p, g, m, v, lr, clip, count, b1, b2, eps, wd):
    g = g * clip
    m = b1 * m + (1 - b1) * g
    v = b2 * v + (1 - b2) * g * g
    c1 = 1 - b1 ** count
    c2 = 1 - b2 ** count
    p = p - lr * ((m / c1) / (jnp.sqrt(v / c2) + eps) + wd * p)
    return p, m, v


@jax.jit
def _sq_sum(tree):
    return sum(jnp.sum(jnp.square(g)) for g in jax.tree.leaves(tree))


def lr_at(step: int, opt: dict) -> float:
    """Linear warm-up over `warmup_steps`, then a cosine from the peak to
    10% of it at `total_steps`."""
    warm = min(1.0, (step + 1) / max(opt["warmup_steps"], 1))
    prog = min(max((step - opt["warmup_steps"])
                   / max(opt["total_steps"] - opt["warmup_steps"], 1), 0.0), 1.0)
    return opt["learning_rate"] * warm * (0.1 + 0.9 * 0.5 * (1 + math.cos(math.pi * prog)))


def layout(cfg: dict) -> weights.Layout:
    vocab_rows = -(-cfg["vocab_size"] // 256) * 256
    return weights.dense_layout(cfg, vocab_rows)


def stacked(lay: weights.Layout) -> List[str]:
    return [p for p, _ in lay if p.startswith("layers/")]


def change_norms(params: Dict[str, jnp.ndarray], seed: int, lay: weights.Layout,
                 vocab: int) -> Dict[str, float]:
    """Norm of (params - the seed's initial params) per leaf and layer; the
    initial leaves are made again one at a time."""
    key = weights.base_key(seed)
    shapes = dict(lay)

    @functools.partial(jax.jit, static_argnames=("path",))
    def norm(p, key, path):
        d = p - weights.make_leaf(key, path, shapes[path])
        d = compare.real_vocab(path, d, vocab)
        if path.startswith("layers/"):
            return jnp.sqrt(jnp.sum(jnp.square(d), axis=tuple(range(1, d.ndim))))
        return jnp.sqrt(jnp.sum(jnp.square(d)))

    out = {p: jax.device_get(norm(params[p], key, path=p)) for p in params}
    return compare.split_layers(out)


def run_reference(cfg: dict, opt: dict, seed: int, batches: List[dict], *,
                  precision: str = "f32", steps: int = 3, device=None,
                  variant: Optional[Callable[[dict], dict]] = None) -> dict:
    """Three AdamW steps of the reference from the seed's weights on
    `batches`. Returns per-step losses and pre-clip global gradient norms,
    the first clipped gradient's norms per leaf, and the parameters'
    change per leaf after `steps` steps. `variant(batch) -> batch` plants a
    fault in the reference's input (used to read faults, never in a run)."""
    device = device or jax.devices()[0]
    with jax.set_mesh(jax.sharding.Mesh(np.array([device]), ("ref",))):
        return _run_reference(cfg, opt, seed, batches, precision, steps, device, variant)


def _run_reference(cfg, opt, seed, batches, precision, steps, device, variant):
    lay = layout(cfg)
    vocab = cfg["vocab_size"]
    sh = jax.sharding.SingleDeviceSharding(device)
    params = weights.make_params(seed, lay, shardings={p: sh for p, _ in lay})
    ref = Reference(cfg, precision)
    host_m, host_v = {}, {}  # Adam's moments between steps, on the host
    losses, gnorms, grad1 = [], [], None
    for step in range(steps):
        batch = batches[step] if variant is None else variant(batches[step])
        toks = jax.device_put(jnp.asarray(batch["tokens"]), sh)
        labs = jax.device_put(jnp.asarray(batch["labels"]), sh)
        loss, grads = ref.grad_fn(params, toks, labs)
        gnorm = float(jnp.sqrt(_sq_sum(grads)))
        clip = min(1.0, opt["grad_clip"] / max(gnorm, 1e-12)) if opt["grad_clip"] > 0 else 1.0
        losses.append(float(loss))
        gnorms.append(gnorm)
        if step == 0:
            grad1 = compare.leaf_norms(grads, vocab, scale=clip, stacked=stacked(lay))
        lr = lr_at(step, opt)
        for p, shape in lay:
            if step == 0:
                m = v = jnp.zeros(shape, jnp.float32, device=sh)
            else:
                m, v = jax.device_put(host_m.pop(p), sh), jax.device_put(host_v.pop(p), sh)
            params[p], m, v = _adam_leaf(
                params[p], grads.pop(p), m, v, np.float32(lr), np.float32(clip),
                np.float32(step + 1), np.float32(opt["beta1"]), np.float32(opt["beta2"]),
                np.float32(opt["eps"]), np.float32(opt["weight_decay"]))
            if step + 1 < steps:  # the last step's moments are not needed
                host_m[p], host_v[p] = np.asarray(m), np.asarray(v)
            del m, v
        del grads
    change = change_norms(params, seed, lay, vocab)
    del params
    return {"losses": losses, "grad_norms": gnorms, "grad1": grad1, "change": change}
