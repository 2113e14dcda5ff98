"""Plain float32 reference of a dense decoder-only LM and its AdamW step.

Written from the published description of the architecture and of AdamW,
with the departures that the configuration file lists (RMSNorm with a
``1 + gamma`` scale, rotary positions, biases on q, k and v only).  It
imports nothing of the program and takes nothing the program made: its
weights come from ``perfbench.lib.weights`` and the seed.

Every matrix product is float32 at ``Precision.HIGHEST``.  Parameters and
AdamW moments are stored in the dtypes the configuration states and every
update is computed in float32, so the reference differs from the program
only by the program's arithmetic.  ``mode="int8"`` is the lower-precision
control: every product, forward and backward, takes its operands rounded to
int8 with one scale per tensor (v5e multiplies int8 at twice its bf16
rate, so this is the step that would tempt a later change);
``mode="fp8"`` rounds them to float8_e4m3 instead.

The step runs layer by layer so that it fits one chip beside its state:
the forward pass keeps each layer's input, a first backward pass takes the
gradient norms (the clip needs the global norm before any update), and a
second backward pass recomputes each layer's gradient and applies AdamW to
that layer.  On several devices the rows of the batch and the leaves of the
state are split over them.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from perfbench.lib import weights

HI = jax.lax.Precision.HIGHEST
_DT = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}
F8_MAX = 448.0          # largest finite float8_e4m3fn


# ---------------------------------------------------------------------------
# Shapes
# ---------------------------------------------------------------------------

def dims(m: dict) -> dict:
    hd = m["head_dim"] or m["d_model"] // m["n_heads"]
    return {"L": m["n_layers"], "D": m["d_model"], "H": m["n_heads"],
            "K": m["n_kv_heads"], "hd": hd, "F": m["d_ff"],
            "V": m["vocab_size"]}


def param_shapes(m: dict) -> dict:
    """{path: ShapeDtypeStruct} of every parameter, stacked over layers."""
    if m["act"] != "gelu":
        raise ValueError(f"reference has no {m['act']!r} MLP")
    d = dims(m)
    L, D, H, K, hd, F, V = (d[k] for k in ("L", "D", "H", "K", "hd", "F", "V"))
    s = {"emb": (V, D), "ln_f": (D,),
         "layers/ln1": (L, D), "layers/ln2": (L, D),
         "layers/attn/wq": (L, D, H * hd), "layers/attn/wk": (L, D, K * hd),
         "layers/attn/wv": (L, D, K * hd), "layers/attn/wo": (L, H * hd, D),
         "layers/mlp/wi": (L, D, F), "layers/mlp/wo": (L, F, D)}
    if m["qkv_bias"]:
        s.update({"layers/attn/bq": (L, H * hd), "layers/attn/bk": (L, K * hd),
                  "layers/attn/bv": (L, K * hd)})
    if not m["tie_embeddings"]:
        s["head"] = (D, V)
    dt = _DT[m["dtype"]]
    return {p: jax.ShapeDtypeStruct(v, dt) for p, v in sorted(s.items())}


# ---------------------------------------------------------------------------
# Products: float32, or a lower-precision control
# ---------------------------------------------------------------------------

def _fp8(x):
    s = jax.lax.stop_gradient(jnp.max(jnp.abs(x)))
    s = jnp.where(s > 0, s / F8_MAX, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _int8(x):
    s = jax.lax.stop_gradient(jnp.max(jnp.abs(x)))
    s = jnp.where(s > 0, s / 127.0, 1.0)
    return jnp.clip(jnp.round(x / s), -127.0, 127.0) * s


def _ein(spec, a, b):
    return jnp.einsum(spec, a, b, precision=HI)


def _quantized(q):
    """A product whose operands, forward and backward, are rounded by q."""
    @partial(jax.custom_vjp, nondiff_argnums=(0,))
    def ein(spec, a, b):
        return _ein(spec, q(a), q(b))

    def fwd(spec, a, b):
        qa, qb = q(a), q(b)
        return _ein(spec, qa, qb), (qa, qb)

    def bwd(spec, res, g):
        _, vjp = jax.vjp(partial(_ein, spec), *res)
        return vjp(q(g))

    ein.defvjp(fwd, bwd)
    return ein


PRODUCTS = {"f32": _ein, "fp8": _quantized(_fp8), "int8": _quantized(_int8)}


# ---------------------------------------------------------------------------
# The model
# ---------------------------------------------------------------------------

def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (1.0 + g)


def _gelu(x):
    return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi)
                                     * (x + 0.044715 * x ** 3)))


def _rope(x, theta):
    """x: (B, S, H, hd); rotates the first half against the second."""
    S, hd = x.shape[1], x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(q, k, v, window, dot, block=512):
    """Causal (and windowed) softmax attention, one block of queries at a
    time over only the keys it may see.  q: (B,S,K,G,hd); k, v: (B,S,K,hd)."""
    S, hd = q.shape[1], q.shape[-1]
    cq = min(S, block)

    def one(q0, qb, kb, vb, k0):
        s = dot("bqkgd,bskd->bkgqs", qb, kb) / math.sqrt(hd)
        qpos = q0 + jnp.arange(qb.shape[1])
        kpos = k0 + jnp.arange(kb.shape[1])
        mask = qpos[:, None] >= kpos[None, :]
        if window:
            mask &= qpos[:, None] - kpos[None, :] < window
        p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
        return dot("bkgqs,bskd->bqkgd", p, vb)

    outs = []
    for q0 in range(0, S, cq):
        k_lo = max(0, q0 - window + 1) if window else 0
        k_hi = q0 + cq
        outs.append(jax.checkpoint(partial(one, q0, k0=k_lo))(
            q[:, q0:q0 + cq], k[:, k_lo:k_hi], v[:, k_lo:k_hi]))
    return jnp.concatenate(outs, axis=1)


def block(lp: dict, x, m: dict, dot):
    """One pre-norm decoder layer.  lp: this layer's float32 leaves."""
    d = dims(m)
    B, S, _ = x.shape
    H, K, hd = d["H"], d["K"], d["hd"]
    h = _rms(x, lp["ln1"], m["norm_eps"])
    q = dot("bsd,de->bse", h, lp["attn/wq"]) + lp.get("attn/bq", 0.0)
    k = dot("bsd,de->bse", h, lp["attn/wk"]) + lp.get("attn/bk", 0.0)
    v = dot("bsd,de->bse", h, lp["attn/wv"]) + lp.get("attn/bv", 0.0)
    q = _rope(q.reshape(B, S, H, hd), m["rope_theta"])
    k = _rope(k.reshape(B, S, K, hd), m["rope_theta"])
    o = _attention(q.reshape(B, S, K, H // K, hd), k, v.reshape(B, S, K, hd),
                   m["sliding_window"], dot)
    x = x + dot("bse,ed->bsd", o.reshape(B, S, H * hd), lp["attn/wo"])
    h = _rms(x, lp["ln2"], m["norm_eps"])
    f = _gelu(dot("bsd,df->bsf", h, lp["mlp/wi"]))
    return x + dot("bsf,fd->bsd", f, lp["mlp/wo"])


def head_loss(x, ln_f, w, tokens, m: dict, dot):
    """Mean next-token cross entropy; the last position has no label."""
    h = _rms(x, ln_f, m["norm_eps"])
    logits = dot("bsd,dv->bsv", h, w)
    labels = jnp.roll(tokens, -1, axis=1)
    mask = jnp.ones(tokens.shape, jnp.float32).at[:, -1].set(0.0)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.sum((logz - gold) * mask) / jnp.sum(mask)


# ---------------------------------------------------------------------------
# Training: layer by layer, two backward passes
# ---------------------------------------------------------------------------

class Reference:
    """The reference trainer on ``devices``, in ``mode`` (a key of PRODUCTS)."""

    def __init__(self, m: dict, opt: dict, devices, mode: str = "f32"):
        if opt["name"] != "adamw":
            raise ValueError(f"reference has no {opt['name']!r} optimizer")
        self.m, self.opt, self.dot = m, opt, PRODUCTS[mode]
        self.shapes = param_shapes(m)
        self.layer_paths = [p for p in self.shapes if p.startswith("layers/")]
        self.mesh = Mesh(np.array(list(devices)), ("d",))
        self.n = len(devices)
        self.mdt = _DT[opt["moment_dtype"]]
        self._jits()

    # -- placement ----------------------------------------------------------
    def _spec(self, shape, stacked):
        first = 1 if stacked else 0
        for ax in range(first, len(shape)):
            if shape[ax] % self.n == 0:
                parts = [None] * len(shape)
                parts[ax] = "d"
                return P(*parts)
        return P()

    def shardings(self) -> dict:
        return {p: NamedSharding(self.mesh, self._spec(s.shape,
                                                       p.startswith("layers/")))
                for p, s in self.shapes.items()}

    def _rows(self, b):
        return NamedSharding(self.mesh, P("d") if b % self.n == 0 else P())

    def _full(self, x):
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(self.mesh, P()))

    # -- compiled pieces ----------------------------------------------------
    def _jits(self):
        m, dot, opt = self.m, self.dot, self.opt
        tied = m["tie_embeddings"]

        def layer(stack, l):
            return {p[len("layers/"):]: self._full(
                jax.lax.dynamic_index_in_dim(a, l, 0, keepdims=False)
                .astype(jnp.float32)) for p, a in stack.items()}

        def fwd(stack, l, x):
            return block(layer(stack, l), x, m, dot)

        def bwd(stack, l, x, dy):
            lp = layer(stack, l)
            _, vjp = jax.vjp(lambda lp_, x_: block(lp_, x_, m, dot), lp, x)
            dlp, dx = vjp(dy)
            sq = {k: jnp.sum(g * g) for k, g in dlp.items()}
            return dx, dlp, sq

        def embed(emb, tokens):
            return jnp.take(emb.astype(jnp.float32), tokens, axis=0)

        def head(x, ln_f, w, tokens):
            w32 = self._full(w.astype(jnp.float32))
            w32 = w32.T if tied else w32
            loss, vjp = jax.vjp(
                lambda x_, g_, w_: head_loss(x_, g_, w_, tokens, m, dot),
                x, ln_f.astype(jnp.float32), w32)
            dx, dg, dw = vjp(jnp.ones((), jnp.float32))
            return loss, dx, dg, dw.T if tied else dw

        def emb_grad(dx0, tokens, dw_emb):
            g = jnp.zeros(self.shapes["emb"].shape, jnp.float32)
            g = g.at[tokens.reshape(-1)].add(dx0.reshape(-1, dx0.shape[-1]))
            return g + dw_emb if tied else g

        def adamw(p, g, mo, v, scale, count):
            b1, b2 = opt["b1"], opt["b2"]
            bc1 = 1.0 - b1 ** count.astype(jnp.float32)
            bc2 = 1.0 - b2 ** count.astype(jnp.float32)
            g = g * scale
            m32 = mo.astype(jnp.float32) * b1 + (1 - b1) * g
            v32 = v.astype(jnp.float32) * b2 + (1 - b2) * g * g
            step = (m32 / bc1) / (jnp.sqrt(v32 / bc2) + opt["eps"])
            if opt["weight_decay"]:
                step = step + opt["weight_decay"] * p.astype(jnp.float32)
            newp = p.astype(jnp.float32) - opt["lr"] * step
            return newp.astype(p.dtype), m32.astype(self.mdt), v32.astype(self.mdt)

        def update_layer(stack, ms, vs, l, dlp, scale, count):
            out = {}
            for p in stack:
                k = p[len("layers/"):]
                cur = [jax.lax.dynamic_index_in_dim(a[p], l, 0, keepdims=False)
                       for a in (stack, ms, vs)]
                new = adamw(cur[0], dlp[k], cur[1], cur[2], scale, count)
                out[p] = [jax.lax.dynamic_update_index_in_dim(a[p], n, l, 0)
                          for a, n in zip((stack, ms, vs), new)]
            return ({p: o[0] for p, o in out.items()},
                    {p: o[1] for p, o in out.items()},
                    {p: o[2] for p, o in out.items()})

        self._fwd = jax.jit(fwd)
        self._bwd = jax.jit(bwd)
        self._embed = jax.jit(embed)
        self._head = jax.jit(head)
        self._emb_grad = jax.jit(emb_grad)
        self._adamw = jax.jit(adamw, donate_argnums=(0, 2, 3))
        self._update_layer = jax.jit(update_layer, donate_argnums=(0, 1, 2))

    # -- one step -----------------------------------------------------------
    def clip_scale(self, norms: dict) -> float:
        """The factor the clip puts on gradients of these leaf norms."""
        gnorm = math.sqrt(sum(n * n for n in norms.values()))
        clip = self.opt["grad_clip"]
        return min(1.0, clip / (gnorm + 1e-9)) if clip > 0 else 1.0

    def _step(self, st: dict, tokens, count: int) -> tuple[float, dict]:
        """One AdamW step in place on ``st``; returns (loss, grad norms)."""
        L = self.m["n_layers"]
        P_, M_, V_ = st["p"], st["m"], st["v"]
        stack = {p: P_[p] for p in self.layer_paths}
        w_out = P_["emb"] if self.m["tie_embeddings"] else P_["head"]
        xs = [self._embed(P_["emb"], tokens)]
        for l in range(L):
            xs.append(self._fwd(stack, l, xs[-1]))
        loss, dx_top, d_lnf, d_w = self._head(xs[-1], P_["ln_f"], w_out, tokens)

        sq = {}
        dx = dx_top
        for l in reversed(range(L)):
            dx, _, s = self._bwd(stack, l, xs[l], dx)
            for k, v in s.items():
                sq["layers/" + k] = sq.get("layers/" + k, 0.0) + v
        grads = {"ln_f": d_lnf}
        if self.m["tie_embeddings"]:
            grads["emb"] = self._emb_grad(dx, tokens, d_w)
        else:
            grads["emb"] = self._emb_grad(dx, tokens, 0.0)
            grads["head"] = d_w
        for p, g in grads.items():
            sq[p] = jnp.sum(jnp.square(g))
        norms = {p: float(jnp.sqrt(v)) for p, v in sq.items()}
        scale = jnp.float32(self.clip_scale(norms))
        cnt = jnp.int32(count)

        for p, g in grads.items():
            P_[p], M_[p], V_[p] = self._adamw(P_[p], g, M_[p], V_[p], scale, cnt)
        del grads
        ms = {p: M_[p] for p in self.layer_paths}
        vs = {p: V_[p] for p in self.layer_paths}
        dx = dx_top
        for l in reversed(range(L)):
            dx, dlp, _ = self._bwd(stack, l, xs[l], dx)
            stack, ms, vs = self._update_layer(stack, ms, vs, l, dlp, scale, cnt)
        P_.update(stack)
        M_.update(ms)
        V_.update(vs)
        return float(loss), norms

    def train(self, key, batches, steps: int = 3) -> dict:
        """``steps`` steps from the seed's weights on ``batches`` (host int32
        arrays, one per step).  Returns the readings that ``compare`` uses:
        each step's loss, each leaf's first gradient norm (before the clip)
        and a sample of its elements, taken from the first moment after one
        step as the program's are, and each leaf's change norm after the
        last step."""
        shard = self.shardings()
        p = dict(weights.make(self.shapes, key, shard))
        zeros = jax.jit(lambda t: {k: jnp.zeros(a.shape, self.mdt)
                                   for k, a in t.items()}, out_shardings=shard)
        st = {"p": p, "m": zeros(p), "v": zeros(p)}
        losses, first, sample = [], None, None
        with jax.default_matmul_precision("highest"):
            for k in range(steps):
                tok = jax.device_put(batches[k], self._rows(batches[k].shape[0]))
                loss, norms = self._step(st, tok, k + 1)
                losses.append(loss)
                if first is None:
                    first, div = norms, (1.0 - self.opt["b1"]) * self.clip_scale(norms)
                    sample = {p: v / div
                              for p, v in weights.sample(st["m"], key).items()}
        change = weights.change_norms(st["p"], key)
        return {"losses": losses, "grad_norms": first, "grad_sample": sample,
                "change_norms": change}
