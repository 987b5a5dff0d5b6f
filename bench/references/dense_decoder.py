"""Plain float32 reference of a dense decoder LM and its STEP training.

Straightforward ``jax.numpy`` at ``jax.default_matmul_precision("highest")``
with no kernels, no cache and no batching tricks, written from the published
architecture and importing nothing of the program:

- token embedding; per layer: LayerNorm, q/k/v projections with biases,
  rotary embedding (half-split rotation, ``rope_theta``), causal grouped
  -query attention, output projection with bias, residual; LayerNorm, MLP
  ``gelu_tanh(x W_fc) W_proj``, residual; final LayerNorm; logits against
  the tied embedding (or the output embedding when untied).
- departures the configuration file records: rotary instead of learned
  positions, no MLP biases.
- N:M masks: keep the ``n`` largest magnitudes of every ``m`` consecutive
  weights along the reduction axis, ties to the lower index.

Weights come from the benchmark's own generator (``harness/weights.py``),
layer by layer, so the reference never holds the program's weights.  The
training reference repeats the STEP recipe: cross-entropy plus the 1e-4
z-loss, gradients by ``jax.grad`` with the straight-through mask in the
mask-learning phase, global-norm clipping, and the two-phase Adam whose
variance freezes into the preconditioner at the switch step.

``mode`` selects the precision: ``"f32"`` (the reference: float32
arithmetic, every leaf stored as the program stores it) or ``"fp8"`` (the
control, one step below the configuration's bfloat16: the leaves stored in
bfloat16 are held in float8_e4m3 with one scale per tensor, and every
weight matmul takes e4m3 operands with a per-row / per-column scale and
e5m2 gradients, accumulated in float32).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from bench.harness import weights as W

MASKED = ("wq", "wk", "wv", "wo", "w_fc", "w_proj")
Z_WEIGHT = 1e-4


# ---------------------------------------------------------------------------
# shapes and weights
# ---------------------------------------------------------------------------


def _dims(config: dict) -> dict:
    m = config["model"]
    return dict(
        L=m["num_hidden_layers"], d=m["hidden_size"],
        h=m["num_attention_heads"], kv=m["num_key_value_heads"],
        hd=m["head_dim"], f=m["intermediate_size"], V=m["vocab_size"],
        theta=float(m["rope_theta"]), eps=float(m["norm_epsilon"]),
        bias=m["attention_bias"], tied=m["tie_word_embeddings"],
        dtype=jnp.dtype(m["dtype"]),
    )


def layer_shapes(config: dict) -> dict:
    """Per-layer leaves, named as the benchmark's generator names them."""
    D = _dims(config)
    d, h, kv, hd, f, dt = D["d"], D["h"], D["kv"], D["hd"], D["f"], D["dtype"]
    s = {
        "pre/norm_scale": ((d,), jnp.float32),
        "pre/norm_bias": ((d,), jnp.float32),
        "attn/wq": ((d, h * hd), dt),
        "attn/wk": ((d, kv * hd), dt),
        "attn/wv": ((d, kv * hd), dt),
        "attn/wo": ((h * hd, d), dt),
        "post/norm_scale": ((d,), jnp.float32),
        "post/norm_bias": ((d,), jnp.float32),
        "mlp/w_fc": ((d, f), dt),
        "mlp/w_proj": ((f, d), dt),
    }
    if D["bias"]:
        s.update({
            "attn/bias_q": ((h * hd,), dt), "attn/bias_k": ((kv * hd,), dt),
            "attn/bias_v": ((kv * hd,), dt), "attn/bias_o": ((d,), dt),
        })
    return s


def top_shapes(config: dict) -> dict:
    D = _dims(config)
    s = {
        "embed/tok_embed": ((D["V"], D["d"]), D["dtype"]),
        "final/norm_scale": ((D["d"],), jnp.float32),
        "final/norm_bias": ((D["d"],), jnp.float32),
    }
    if not D["tied"]:
        s["unembed/out_embed"] = ((D["d"], D["V"]), D["dtype"])
    return s


def nm_mask(w, n: int, m: int):
    """Keep the n largest |w| of every m consecutive rows (axis -2)."""
    k, o = w.shape[-2], w.shape[-1]
    g = jnp.abs(w.astype(jnp.float32)).reshape(w.shape[:-2] + (k // m, m, o))
    # rank of each element in its group: larger first, ties to lower index
    gt = (g[..., None, :, :] > g[..., :, None, :]) | (
        (g[..., None, :, :] == g[..., :, None, :])
        & (jnp.arange(m)[None, :, None] < jnp.arange(m)[:, None, None])
    )
    rank = jnp.sum(gt, axis=-2)  # elements ranked above each one
    return (rank < n).reshape(w.shape)


def stored(config: dict) -> dict:
    """Every leaf's stored dtype, named as the program names it."""
    out = {name: dt for name, (_, dt) in top_shapes(config).items()}
    out.update({"body/sb_0/" + name: dt
                for name, (_, dt) in layer_shapes(config).items()})
    return out


@functools.partial(jax.jit, static_argnums=(0,))
def _tree(ck: str, key) -> dict:
    """Every leaf as stored, named as the program names it, body leaves
    stacked over layers.  Each layer is drawn by its own call with its
    layer number, as the program's tree is (``harness/weights.py``)."""
    config = _CONFIGS[ck]
    L = _dims(config)["L"]
    tree = {name: W.leaf(key, name, shape, dt, stacked=False)
            for name, (shape, dt) in top_shapes(config).items()}
    for name, (shape, dt) in layer_shapes(config).items():
        tree["body/sb_0/" + name] = jnp.stack(
            [W.leaf(key, "body/sb_0/" + name, shape, dt, stacked=True,
                    layer=l) for l in range(L)])
    return tree


_CONFIGS: dict = {}


def _register(config: dict) -> str:
    import json

    k = json.dumps(config, sort_keys=True)
    _CONFIGS[k] = config
    return k


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _q8(x, axis, dtype=jnp.float8_e4m3fn):
    """Round to fp8 with a scale per slice along ``axis`` (per tensor when
    ``axis`` is None), back in float32."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=axis is not None)
    s = jnp.where(amax > 0, amax / float(jnp.finfo(dtype).max), 1.0)
    return (x / s).astype(dtype).astype(jnp.float32) * s


@jax.custom_vjp
def _mm8(x, w):
    return _mm8_fwd(x, w)[0]


def _mm8_fwd(x, w):
    xq, wq = _q8(x, -1), _q8(w, -2)
    return jnp.matmul(xq, wq, precision=jax.lax.Precision.HIGHEST), (xq, wq)


def _mm8_bwd(res, dy):
    xq, wq = res
    g = _q8(dy, None, jnp.float8_e5m2)  # gradients in e5m2, one scale
    dx = jnp.matmul(g, wq.T, precision=jax.lax.Precision.HIGHEST)
    dw = jnp.einsum("...k,...o->ko", xq, g, precision=jax.lax.Precision.HIGHEST)
    return dx, dw


_mm8.defvjp(_mm8_fwd, _mm8_bwd)


def mm(x, w, mode: str):
    """A weight matmul: float32 at the highest precision, or the control's
    fp8 (e4m3 operands scaled per row of ``x`` and per column of ``w``;
    its gradients through e5m2 with one scale), accumulated in float32."""
    if mode == "fp8":
        return _mm8(x, w)
    return jnp.matmul(x, w, precision=jax.lax.Precision.HIGHEST)


def layernorm(x, scale, bias, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * scale + bias


def rope(x, pos, theta):
    """x (B, S, H, D); half-split rotation."""
    d = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = pos[:, :, None, None].astype(jnp.float32) * inv
    c, s = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def attention(q, k, v, block: int = 512):
    """Causal GQA, q (B, S, H, D), k/v (B, S, KV, D), in query blocks."""
    b, s, h, d = q.shape
    kv = k.shape[2]
    qg = q.reshape(b, s, kv, h // kv, d)
    outs = []
    for q0 in range(0, s, block):
        qb = qg[:, q0:q0 + block]
        sc = jnp.einsum("bqkgd,bskd->bkgqs", qb, k,
                        precision=jax.lax.Precision.HIGHEST) * d ** -0.5
        qpos = q0 + jnp.arange(qb.shape[1])
        sc = jnp.where(jnp.arange(s)[None, :] <= qpos[:, None], sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        o = jnp.einsum("bkgqs,bskd->bqkgd", p, v,
                       precision=jax.lax.Precision.HIGHEST)
        outs.append(o.reshape(b, qb.shape[1], h, d))
    return jnp.concatenate(outs, axis=1)


def layer(config: dict, x, p: dict, mode: str):
    D = _dims(config)
    p = {k: v.astype(jnp.float32) for k, v in p.items()}
    b, s, _ = x.shape
    pos = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    h = layernorm(x, p["pre/norm_scale"], p["pre/norm_bias"], D["eps"])
    q, k, v = (mm(h, p[f"attn/w{n}"], mode) for n in "qkv")
    if D["bias"]:
        q, k, v = q + p["attn/bias_q"], k + p["attn/bias_k"], v + p["attn/bias_v"]
    q = rope(q.reshape(b, s, D["h"], D["hd"]), pos, D["theta"])
    k = rope(k.reshape(b, s, D["kv"], D["hd"]), pos, D["theta"])
    v = v.reshape(b, s, D["kv"], D["hd"])
    a = attention(q, k, v).reshape(b, s, D["h"] * D["hd"])
    a = mm(a, p["attn/wo"], mode)
    if D["bias"]:
        a = a + p["attn/bias_o"]
    x = x + a
    h = layernorm(x, p["post/norm_scale"], p["post/norm_bias"], D["eps"])
    h = jax.nn.gelu(mm(h, p["mlp/w_fc"], mode), approximate=True)
    return x + mm(h, p["mlp/w_proj"], mode)


def head(config: dict, x, top: dict, mode: str):
    D = _dims(config)
    top = {k: v.astype(jnp.float32) for k, v in top.items()}
    x = layernorm(x, top["final/norm_scale"], top["final/norm_bias"], D["eps"])
    w = top["embed/tok_embed"].T if D["tied"] else top["unembed/out_embed"]
    return mm(x, w, mode)


# ---------------------------------------------------------------------------
# training reference: STEP steps from the seed
# ---------------------------------------------------------------------------


def all_weights(config: dict, seed: int) -> dict:
    """The whole tree as the program names it, float32 values of the
    stored weights; body leaves stacked over layers."""
    return {k: v.astype(jnp.float32)
            for k, v in _tree(_register(config), W.seed_key(seed)).items()}


def loss(config: dict, tree: dict, tokens, labels, mode: str = "f32"):
    """Mean cross-entropy + 1e-4 mean z-loss over a batch."""
    L = _dims(config)["L"]
    top = {k: v for k, v in tree.items() if not k.startswith("body/")}
    x = top["embed/tok_embed"][tokens]
    for l in range(L):
        p = {k[len("body/sb_0/"):]: v[l] for k, v in tree.items()
             if k.startswith("body/")}
        x = jax.checkpoint(lambda x, p: layer(config, x, p, mode))(x, p)
    lg = head(config, x, top, mode)
    lse = jax.nn.logsumexp(lg, -1)
    ll = jnp.take_along_axis(lg, labels[..., None], -1)[..., 0]
    return jnp.mean(lse - ll) + Z_WEIGHT * jnp.mean(jnp.square(lse))


@functools.partial(jax.jit, static_argnums=(0,))
def _masks(ck: str, tree: dict) -> dict:
    config = _CONFIGS[ck]
    sp = config["sparsity"]
    return {k: (nm_mask(v, sp["n"], sp["m"]) if k.rsplit("/", 1)[-1] in MASKED
                else jnp.ones(v.shape, bool)) for k, v in tree.items()}


def masks(config: dict, tree: dict) -> dict:
    return _masks(_register(config), tree)


def _rounder(code: str):
    """How a leaf is stored: its dtype (``"float32"``, ``"bfloat16"``), or
    the control's ``"fp8"`` (e4m3 with one scale per tensor)."""
    if code == "fp8":
        return lambda x: _q8(x, None)
    dt = jnp.dtype(code)
    return lambda x: x.astype(dt).astype(jnp.float32)


@functools.partial(jax.jit, static_argnums=(0, 1))
def _adam(opt: tuple, codes: tuple, t, tree, m, v, pre, g):
    """One STEP update from the gradient ``g`` at step ``t`` (1-based):
    global-norm clipping; the first moment always; the second moment and
    the live Adam direction up to the switch, whose variance freezes into
    the preconditioner; then the frozen direction.  As the program's
    ``p + u.astype(p.dtype)``, the update is rounded to the leaf's stored
    form (``codes``: name and form) and added in it."""
    lr, b1, b2, eps, clip, switch = opt
    gn = jnp.sqrt(sum(jnp.sum(jnp.square(x)) for x in g.values()))
    scale = jnp.minimum(1.0, clip / (gn + 1e-12))
    g = {k: x * scale for k, x in g.items()}
    tf = t.astype(jnp.float32)
    bc1, bc2 = 1 - b1 ** tf, 1 - b2 ** tf
    phase2 = t > switch
    out = {}
    for k, code in codes:
        rnd = _rounder(code)
        mk = b1 * m[k] + (1 - b1) * g[k]
        vk = jnp.where(phase2, v[k], b2 * v[k] + (1 - b2) * jnp.square(g[k]))
        pk = jnp.where(t == switch, jnp.sqrt(vk / bc2) + eps, pre[k])
        upd = jnp.where(phase2, (mk / bc1) / pk,
                        (mk / bc1) / (jnp.sqrt(vk / bc2) + eps))
        out[k] = (rnd(tree[k] + rnd(-lr * upd)), mk, vk, pk,
                  jnp.linalg.norm(g[k].ravel()))
    return tuple({k: o[i] for k, o in out.items()} for i in range(5))


_GRADS: dict = {}


def _grads(config: dict, mode: str, rows: int):
    """Jitted (loss, gradients) of a whole batch in blocks of ``rows``
    rows, the mean over blocks; masked weights take the straight-through
    gradient (the forward sees ``mask * w``, the gradient reaches ``w``)."""
    k = (_register(config), mode, rows)
    if k not in _GRADS:
        def f(tree, mk, tok, lab):
            nb = tok.shape[0] // rows
            tok = tok.reshape((nb, rows) + tok.shape[1:])
            lab = lab.reshape((nb, rows) + lab.shape[1:])

            def lossf(p, t, l):
                fp = {n: p[n] + jax.lax.stop_gradient(
                    jnp.where(mk[n], p[n], 0.0) - p[n]) for n in p}
                return loss(config, fp, t, l, mode)

            def one(acc, tl):
                lv, g = jax.value_and_grad(lossf)(tree, *tl)
                return (acc[0] + lv, {n: acc[1][n] + g[n] for n in g}), None

            init = (jnp.zeros((), jnp.float32),
                    {n: jnp.zeros_like(v) for n, v in tree.items()})
            (ls, gs), _ = jax.lax.scan(one, init, (tok, lab))
            return ls / nb, {n: v / nb for n, v in gs.items()}

        _GRADS[k] = jax.jit(f)
    return _GRADS[k]


def train_steps(config: dict, seed: int, batches: list, opt: dict,
                mode: str = "f32", rows_per_block: int = 1) -> dict:
    """Run ``len(batches)`` STEP steps from the seed's weights.

    ``opt``: ``lr``, ``b1``, ``b2``, ``eps``, ``clip``, ``switch_at`` (the
    step whose variance freezes into the preconditioner; masking starts
    the step after).  Every leaf is stored as the program stores it; in
    the control (``mode="fp8"``) the leaves stored in the configuration's
    ``dtype`` are held in fp8 instead and every weight matmul takes fp8
    operands.  Returns the per-step losses and phases, per leaf the norm
    of the first step's gradient as the optimizer gets it (clipped), and
    per leaf as float32 arrays on the host the parameters' change after
    the last step and the frozen preconditioner P*."""
    low = _dims(config)["dtype"]
    codes = tuple(sorted(
        (k, "fp8" if mode == "fp8" and dt == low else jnp.dtype(dt).name)
        for k, dt in stored(config).items()))
    w0 = all_weights(config, seed)
    tree = {k: _rounder(c)(w0[k]) for k, c in codes}
    p0 = tree
    m = {k: jnp.zeros_like(v) for k, v in tree.items()}
    v2, pre = dict(m), {k: jnp.ones_like(v) for k, v in tree.items()}
    hyper = (opt["lr"], opt["b1"], opt["b2"], opt["eps"], opt["clip"],
             opt["switch_at"])
    ones = {k: jnp.ones(v.shape, bool) for k, v in tree.items()}
    grads = _grads(config, mode, rows_per_block)
    losses, phase2, g1 = [], [], None
    for t, batch in enumerate(batches, start=1):
        mk = masks(config, tree) if t > opt["switch_at"] else ones
        with jax.default_matmul_precision("highest"):
            lv, g = grads(tree, mk, jnp.asarray(batch["tokens"]),
                          jnp.asarray(batch["labels"]))
        tree, m, v2, pre, gnorm = _adam(hyper, codes, jnp.asarray(t), tree,
                                        m, v2, pre, g)
        losses.append(float(lv))
        phase2.append(int(t > opt["switch_at"]))
        if t == 1:
            g1 = {k: float(x) for k, x in gnorm.items()}
    change = jax.device_get(_change(tree, p0))
    return {"losses": losses, "phase2": phase2, "grad_norms": g1,
            "change": change, "precond": jax.device_get(pre)}


@jax.jit
def _change(a, b):
    return {k: a[k] - b[k] for k in a}
