"""The architecture zoo's single entry point: a configurable decoder LM.

One code path covers all 10 assigned architectures:

- mixers: GQA attention (RoPE / M-RoPE / QKV-bias / sliding window),
  MLA (DeepSeek latent attention), Mamba-2 SSD, RG-LRU (Griffin).
- MLPs: SwiGLU, GeLU, MoE (top-k, shared experts), or none (Mamba-2).
- heterogenous stacks via ``layer_plan``: a periodic super-block is scanned
  (``lax.scan`` keeps HLO size O(1) in depth — 80-layer dry-runs compile),
  with optional non-periodic head/tail layers applied individually
  (DeepSeek's dense first layer; RecurrentGemma's 38 = 12×(rec,rec,attn)+2).

The model is sparsity-agnostic in two senses: during training, recipes mask
the *parameter tree* before it reaches ``forward`` (see core/recipes.py),
exactly like the paper applies Π⊙w per training step; at serving time, the
parameter tree may hold ``sparse_infer.CompressedTensor`` leaves — every
weight matmul dispatches through ``layers.matmul``, so ``prefill`` and
``decode_step`` run directly on the N:M-compressed artifact (the
``repro.serving`` engine's fast path; no dense rehydration in HBM).
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.kernels import dispatch
from repro.models import cache as C
from repro.models import layers as L
from repro.models import mla as MLA
from repro.models import moe as MOE
from repro.models import recurrent as REC
from repro.models import ssm as SSM

# ---------------------------------------------------------------------------
# layer plan
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class LayerPlan:
    head: tuple[str, ...]  # kinds of unscanned leading layers
    period: tuple[str, ...]  # the scanned super-block's kinds
    n_body: int  # number of scanned super-blocks
    tail: tuple[str, ...]  # kinds of unscanned trailing layers


def layer_plan(cfg: ArchConfig) -> LayerPlan:
    kinds = cfg.block_kinds()
    head: list[str] = []
    if cfg.moe is not None and cfg.moe.first_layer_dense:
        head = [kinds[0] + ":dense"]
        kinds = kinds[1:]
    if cfg.layer_pattern is None:
        period = (kinds[0],) if kinds else ()
        return LayerPlan(tuple(head), period, len(kinds), ())
    p = len(cfg.layer_pattern)
    n_body = len(kinds) // p
    tail = tuple(kinds[n_body * p :])
    return LayerPlan(tuple(head), tuple(cfg.layer_pattern), n_body, tail)


def _block_mixer_mlp(kind: str, cfg: ArchConfig) -> tuple[str, str]:
    """kind string -> (mixer, mlp_kind)."""
    force_dense = kind.endswith(":dense")
    base = kind.split(":")[0]
    if base == "ssm":
        mixer = "ssm"
        mlp = "none"
    elif base == "rec":
        mixer = "rec"
        mlp = "dense"
    else:  # attn
        mixer = "mla" if cfg.mla is not None else "attn"
        mlp = "moe" if (cfg.moe is not None and not force_dense) else "dense"
    if force_dense:
        mlp = "dense"
    return mixer, mlp


# ---------------------------------------------------------------------------
# parameter init
# ---------------------------------------------------------------------------


def _init_norm(cfg: ArchConfig, d: int) -> dict:
    if cfg.norm == "rms":
        return {"norm_scale": jnp.zeros((d,), jnp.float32)}
    return {
        "norm_scale": jnp.ones((d,), jnp.float32),
        "norm_bias": jnp.zeros((d,), jnp.float32),
    }


def _apply_norm(cfg: ArchConfig, p: dict, x: jnp.ndarray) -> jnp.ndarray:
    if cfg.norm == "rms":
        return L.rmsnorm(x, p["norm_scale"])
    return L.layernorm(x, p["norm_scale"], p["norm_bias"])


def _init_attn(key, cfg: ArchConfig, dtype) -> dict:
    d, h, kv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv, cfg.hd
    ks = jax.random.split(key, 4)
    p = {
        "wq": L.dense_init(ks[0], d, h * hd, dtype),
        "wk": L.dense_init(ks[1], d, kv * hd, dtype),
        "wv": L.dense_init(ks[2], d, kv * hd, dtype),
        "wo": L.dense_init(ks[3], h * hd, d, dtype),
    }
    if cfg.qkv_bias:
        p["bias_q"] = jnp.zeros((h * hd,), dtype)
        p["bias_k"] = jnp.zeros((kv * hd,), dtype)
        p["bias_v"] = jnp.zeros((kv * hd,), dtype)
    if cfg.o_bias:
        p["bias_o"] = jnp.zeros((d,), dtype)
    return p


def _init_mlp(key, cfg: ArchConfig, dtype) -> dict:
    d, f = cfg.d_model, cfg.d_ff
    ks = jax.random.split(key, 3)
    if cfg.mlp == "swiglu":
        return {
            "w_gate": L.dense_init(ks[0], d, f, dtype),
            "w_up": L.dense_init(ks[1], d, f, dtype),
            "w_down": L.dense_init(ks[2], f, d, dtype),
        }
    return {
        "w_fc": L.dense_init(ks[0], d, f, dtype),
        "w_proj": L.dense_init(ks[1], f, d, dtype),
    }


def _init_block(key, kind: str, cfg: ArchConfig, dtype) -> dict:
    mixer, mlp = _block_mixer_mlp(kind, cfg)
    d = cfg.d_model
    k1, k2, k3 = jax.random.split(key, 3)
    p: dict = {"pre": _init_norm(cfg, d)}
    if mixer == "attn":
        p["attn"] = _init_attn(k1, cfg, dtype)
    elif mixer == "mla":
        p["attn"] = MLA.init_mla_params(k1, d, cfg.n_heads, cfg.mla, dtype)
    elif mixer == "ssm":
        p["mixer"] = SSM.init_ssm_params(k1, d, cfg.ssm, dtype)
    elif mixer == "rec":
        p["mixer"] = REC.init_rglru_params(k1, d, cfg.rglru, dtype)
    if mlp != "none":
        p["post"] = _init_norm(cfg, d)
        if mlp == "moe":
            p["moe"] = MOE.init_moe_params(k2, d, cfg.moe, dtype)
        else:
            p["mlp"] = _init_mlp(k2, cfg, dtype)
    return p


def init_params(cfg: ArchConfig, key) -> dict:
    dtype = jnp.dtype(cfg.param_dtype)
    plan = layer_plan(cfg)
    keys = jax.random.split(key, 8)
    params: dict = {
        "embed": {"tok_embed": L.embed_init(keys[0], cfg.vocab, cfg.d_model, dtype)},
        "final": _init_norm(cfg, cfg.d_model),
    }
    if not cfg.tie_embeddings:
        params["unembed"] = {
            "out_embed": L.dense_init(keys[1], cfg.d_model, cfg.vocab, dtype)
        }
    if cfg.frontend != "none":
        fdim = frontend_dim(cfg)
        params["frontend"] = {
            "frontend_proj": L.dense_init(keys[2], fdim, cfg.d_model, dtype)
        }
    for i, kind in enumerate(plan.head):
        params[f"head_{i}"] = _init_block(
            jax.random.fold_in(keys[3], i), kind, cfg, dtype
        )
    if plan.n_body:
        def one(k):
            sb = {}
            for j, kind in enumerate(plan.period):
                sb[f"sb_{j}"] = _init_block(jax.random.fold_in(k, j), kind, cfg, dtype)
            return sb

        body_keys = jax.random.split(keys[4], plan.n_body)
        params["body"] = jax.vmap(one)(body_keys)
    for i, kind in enumerate(plan.tail):
        params[f"tail_{i}"] = _init_block(
            jax.random.fold_in(keys[5], i), kind, cfg, dtype
        )
    return params


def frontend_dim(cfg: ArchConfig) -> int:
    return {"audio_stub": 512, "vision_stub": 1176}.get(cfg.frontend, 0)


# ---------------------------------------------------------------------------
# forward (full sequence: train / prefill)
# ---------------------------------------------------------------------------


def _attn_forward(
    x, p, cfg: ArchConfig, positions, *, chunk: int, want_cache: bool
):
    b, s, d = x.shape
    h, kv, hd = cfg.n_heads, cfg.n_kv, cfg.hd
    q = L.matmul(x, p["wq"])
    k = L.matmul(x, p["wk"])
    v = L.matmul(x, p["wv"])
    if cfg.qkv_bias:
        q, k, v = q + p["bias_q"], k + p["bias_k"], v + p["bias_v"]
    q = q.reshape(b, s, h, hd)
    k = k.reshape(b, s, kv, hd)
    v = v.reshape(b, s, kv, hd)
    if cfg.rope == "rope":
        q = L.apply_rope(q, positions, cfg.rope_theta)
        k = L.apply_rope(k, positions, cfg.rope_theta)
    elif cfg.rope == "mrope":
        q = L.apply_mrope(q, positions, theta=cfg.rope_theta)
        k = L.apply_mrope(k, positions, theta=cfg.rope_theta)
    out = L.chunked_attention(
        q, k, v, causal=True, window=cfg.local_window, chunk=chunk
    )
    out = L.matmul(out.reshape(b, s, h * hd), p["wo"])
    if cfg.o_bias:
        out = out + p["bias_o"]
    cache = (k, v) if want_cache else None
    return out, cache


def _block_forward(
    x,
    p: dict,
    kind: str,
    cfg: ArchConfig,
    positions,
    *,
    chunk: int = 512,
    want_cache: bool = False,
    ep_constraint=None,
):
    """Full-seq block. Returns (x_out, aux_loss, cache_entry)."""
    mixer, mlp = _block_mixer_mlp(kind, cfg)
    aux = jnp.zeros((), jnp.float32)
    cache = None
    h = _apply_norm(cfg, p["pre"], x)
    if mixer == "attn":
        with jax.named_scope("attention"):
            mix_out, cache = _attn_forward(
                h, p["attn"], cfg, positions, chunk=chunk, want_cache=want_cache
            )
    elif mixer == "mla":
        pos1d = positions if positions.ndim == 2 else positions[..., 0]
        mix_out, lat = MLA.mla_attention(
            h, p["attn"], cfg.n_heads, cfg.mla, pos1d, cfg.rope_theta, chunk
        )
        cache = lat if want_cache else None
    elif mixer == "ssm":
        mix_out, state = SSM.ssm_block(h, p["mixer"], cfg.d_model, cfg.ssm)
        cache = state if want_cache else None  # (ssm_state, conv_tail)
    elif mixer == "rec":
        mix_out, state, conv_state = REC.rglru_block(h, p["mixer"], cfg.rglru)
        cache = (state, conv_state) if want_cache else None
    else:
        raise AssertionError(mixer)
    x = x + mix_out
    if mlp != "none":
        h2 = _apply_norm(cfg, p["post"], x)
        with jax.named_scope("mlp"):
            if mlp == "moe":
                mo, a = MOE.moe_mlp(
                    h2, p["moe"], cfg.moe, ep_constraint=ep_constraint
                )
                aux = aux + a
            elif cfg.mlp == "swiglu":
                mo = L.swiglu_mlp(h2, p["mlp"])
            else:
                mo = L.gelu_mlp(h2, p["mlp"])
        x = x + mo
    return x, aux, cache


def _default_positions(cfg: ArchConfig, b: int, s: int, offset=0):
    pos = offset + jnp.arange(s)[None, :]
    pos = jnp.broadcast_to(pos, (b, s))
    if cfg.rope == "mrope":
        return jnp.broadcast_to(pos[..., None], (b, s, 3))
    return pos


def forward(
    params: dict,
    cfg: ArchConfig,
    batch: dict,
    *,
    chunk: int = 512,
    remat: bool = True,
    want_cache: bool = False,
    block_constraint=None,
    ep_constraint=None,
) -> tuple[jnp.ndarray, jnp.ndarray, Any]:
    """Full-sequence forward.

    ``batch``: {"tokens": (B,S) int32} or {"embeds": (B,S,F)} for stub
    frontends; optional {"positions"}. Returns (logits, aux_loss, caches).

    ``block_constraint``: optional fn applied to the residual stream at
    layer boundaries — the launch layer injects
    ``lax.with_sharding_constraint`` here (e.g. sequence-parallel residuals),
    which pins the remat-saved activations' layout under pjit.
    """
    plan = layer_plan(cfg)
    if "embeds" in batch and cfg.frontend != "none":
        x = L.matmul(batch["embeds"], params["frontend"]["frontend_proj"])
        b, s = x.shape[0], x.shape[1]
    else:
        tokens = batch["tokens"]
        b, s = tokens.shape
        with jax.named_scope("lm_head"):
            x = params["embed"]["tok_embed"][tokens]
    positions = batch.get("positions")
    if positions is None:
        positions = _default_positions(cfg, b, s)

    if block_constraint is not None:
        x = block_constraint(x)
    aux = jnp.zeros((), jnp.float32)
    caches: dict = {}

    with jax.named_scope("layers"):
        for i, kind in enumerate(plan.head):
            x, a, c = _block_forward(
                x, params[f"head_{i}"], kind, cfg, positions,
                chunk=chunk, want_cache=want_cache, ep_constraint=ep_constraint,
            )
            aux += a
            if want_cache:
                caches[f"head_{i}"] = c

        if plan.n_body:
            def superblock(x, p_sb):
                a_tot = jnp.zeros((), jnp.float32)
                cs = {}
                for j, kind in enumerate(plan.period):
                    x, a, c = _block_forward(
                        x, p_sb[f"sb_{j}"], kind, cfg, positions,
                        chunk=chunk, want_cache=want_cache, ep_constraint=ep_constraint,
                    )
                    a_tot += a
                    if want_cache:
                        cs[f"sb_{j}"] = c
                if block_constraint is not None:
                    x = block_constraint(x)
                return x, (a_tot, cs if want_cache else None)

            sb_fn = jax.checkpoint(superblock) if remat else superblock

            def scan_body(x, p_sb):
                return sb_fn(x, p_sb)

            x, (a_list, c_stack) = jax.lax.scan(scan_body, x, params["body"])
            aux += jnp.sum(a_list)
            if want_cache:
                caches["body"] = c_stack

        for i, kind in enumerate(plan.tail):
            x, a, c = _block_forward(
                x, params[f"tail_{i}"], kind, cfg, positions,
                chunk=chunk, want_cache=want_cache, ep_constraint=ep_constraint,
            )
            aux += a
            if want_cache:
                caches[f"tail_{i}"] = c

    with jax.named_scope("lm_head"):
        x = _apply_norm(cfg, params["final"], x)
        if cfg.tie_embeddings:
            logits = x @ params["embed"]["tok_embed"].T  # embeddings stay dense
        else:
            logits = L.matmul(x, params["unembed"]["out_embed"])
    return logits, aux, (caches if want_cache else None)


def loss_fn(
    params: dict, cfg: ArchConfig, batch: dict, *, chunk: int = 512,
    remat: bool = True, aux_weight: float = 0.01, z_weight: float = 1e-4,
    block_constraint=None, ep_constraint=None, logits_constraint=None,
) -> tuple[jnp.ndarray, dict]:
    logits, aux, _ = forward(params, cfg, batch, chunk=chunk, remat=remat,
                             block_constraint=block_constraint,
                             ep_constraint=ep_constraint)
    if logits_constraint is not None:
        # keep logits vocab-sharded through the loss: logsumexp reduces over
        # the sharded vocab dim (GSPMD psums a (B,S) scalar field instead of
        # all-gathering the (B,S,V) logits — §Perf hillclimb #1)
        logits = logits_constraint(logits)
    labels = batch["labels"]
    mask = batch.get("loss_mask")
    with jax.named_scope("lm_head"):
        lf = logits.astype(jnp.float32)
        lse = jax.nn.logsumexp(lf, axis=-1)
        ll = jnp.take_along_axis(lf, labels[..., None], axis=-1)[..., 0]
        nll = lse - ll
        zloss = jnp.square(lse)
        if mask is not None:
            denom = jnp.maximum(jnp.sum(mask), 1.0)
            ce = jnp.sum(nll * mask) / denom
            zl = jnp.sum(zloss * mask) / denom
        else:
            ce = jnp.mean(nll)
            zl = jnp.mean(zloss)
        total = ce + aux_weight * aux + z_weight * zl
    return total, {"ce": ce, "aux": aux, "zloss": zl}


# ---------------------------------------------------------------------------
# KV-cache serving path
# ---------------------------------------------------------------------------


def init_cache(
    cfg: ArchConfig, batch_size: int, max_len: int, dtype=None, layout=None
) -> dict:
    """Allocate the decode cache for every layer.

    ``layout`` (default :class:`models.cache.SlabLayout`) owns the storage
    geometry of attention / MLA entries — contiguous per-lane slabs or a
    paged ``(num_pages, n_kv, page_size, head_dim)`` pool with page tables.
    SSM and RG-LRU states are O(1) per lane and stay slot-indexed either
    way.
    """
    dtype = dtype or jnp.dtype(cfg.param_dtype)
    if layout is None:
        layout = C.SlabLayout(max_len)
    plan = layer_plan(cfg)

    def one(kind: str) -> Any:
        mixer, _ = _block_mixer_mlp(kind, cfg)
        if mixer == "attn":
            return layout.attn_alloc(
                batch_size, cfg.local_window, cfg.n_kv, cfg.hd, dtype
            )
        if mixer == "mla":
            return layout.mla_alloc(
                batch_size, cfg.mla.kv_lora, cfg.mla.rope_head_dim, dtype
            )
        if mixer == "ssm":
            dims = SSM.ssm_dims(cfg.d_model, cfg.ssm)
            conv_dim = dims["d_inner"] + 2 * cfg.ssm.n_groups * cfg.ssm.d_state
            return {
                "state": jnp.zeros(
                    (batch_size, dims["n_heads"], cfg.ssm.head_dim, cfg.ssm.d_state),
                    jnp.float32,
                ),
                "conv": jnp.zeros(
                    (batch_size, cfg.ssm.conv_width - 1, conv_dim), dtype
                ),
            }
        if mixer == "rec":
            w = cfg.rglru.lru_width
            return {
                "state": jnp.zeros((batch_size, w), jnp.float32),
                "conv": jnp.zeros(
                    (batch_size, cfg.rglru.conv_width - 1, w), dtype
                ),
            }
        raise AssertionError(mixer)

    cache: dict = {"len": jnp.zeros((batch_size,), jnp.int32)}
    for i, kind in enumerate(plan.head):
        cache[f"head_{i}"] = one(kind)
    if plan.n_body:
        sb = {f"sb_{j}": one(kind) for j, kind in enumerate(plan.period)}
        cache["body"] = jax.tree_util.tree_map(
            lambda x: jnp.broadcast_to(x[None], (plan.n_body,) + x.shape).copy(), sb
        )
    for i, kind in enumerate(plan.tail):
        cache[f"tail_{i}"] = one(kind)
    tables = layout.tables(batch_size)
    if tables is not None:
        cache["tables"] = tables
    return cache


def write_prefill(
    cache: dict, cfg: ArchConfig, produced: dict, lanes, lens, layout=None
) -> dict:
    """Write a batch of freshly prefilled rows into the serving cache pool.

    ``produced`` is the per-layer cache tuple tree from
    ``forward(want_cache=True)`` over the (possibly padded) prompt batch;
    row ``r`` is valid up to ``lens[r]`` tokens and lands in lane
    ``lanes[r]`` (a lane index ≥ the pool's batch size marks a padding row
    and is dropped).  The layout owns the attention/MLA storage geometry;
    SSM / RG-LRU states scatter into their lanes directly, so recurrent
    rows must be *exact length* (``lens[r] == prompt length``) — the
    engine pads only attention-family archs.
    """
    if layout is None:
        layout = C.SlabLayout()
    plan = layer_plan(cfg)
    tables = cache.get("tables")

    def wr(kind: str, c, pr):
        mixer, _ = _block_mixer_mlp(kind, cfg)
        if mixer == "attn":
            k, v = pr
            return layout.attn_write_rows(
                c, k, v, lanes, lens, tables, cfg.local_window
            )
        if mixer == "mla":
            ckv, krope = pr
            return layout.mla_write_rows(c, ckv, krope, lanes, lens, tables)
        if mixer == "ssm":
            st, tail = pr
            # short prompts: left-pad the conv tail with zeros
            w1 = c["conv"].shape[1]
            tail = tail.astype(c["conv"].dtype)
            if tail.shape[1] < w1:
                pad = jnp.zeros(
                    (tail.shape[0], w1 - tail.shape[1], tail.shape[2]), tail.dtype
                )
                tail = jnp.concatenate([pad, tail], axis=1)
            return {
                "state": c["state"].at[lanes].set(st, mode="drop"),
                "conv": c["conv"].at[lanes].set(tail, mode="drop"),
            }
        if mixer == "rec":
            st, cv = pr
            return {
                "state": c["state"].at[lanes].set(st, mode="drop"),
                "conv": c["conv"].at[lanes].set(
                    cv.astype(c["conv"].dtype), mode="drop"
                ),
            }
        raise AssertionError(mixer)

    out = dict(cache)
    out["len"] = cache["len"].at[lanes].set(lens, mode="drop")
    for i, kind in enumerate(plan.head):
        out[f"head_{i}"] = wr(kind, cache[f"head_{i}"], produced[f"head_{i}"])
    if plan.n_body:
        def wr_sb(c_sb, pr_sb):
            return {
                f"sb_{j}": wr(kind, c_sb[f"sb_{j}"], pr_sb[f"sb_{j}"])
                for j, kind in enumerate(plan.period)
            }

        out["body"] = jax.vmap(wr_sb)(cache["body"], produced["body"])
    for i, kind in enumerate(plan.tail):
        out[f"tail_{i}"] = wr(kind, cache[f"tail_{i}"], produced[f"tail_{i}"])
    return out


def reset_lanes(cfg: ArchConfig, cache: dict, mask) -> dict:
    """Zero the recurrent (SSM / RG-LRU) state rows of masked lanes.

    The device-resident scheduler refills a freed lane *inside* the decode
    loop: paged/slab attention entries need no reset — stale KV is dead
    under the lane's length mask once ``cache["len"]`` rewinds to 0 — but
    O(1) recurrent states are read unconditionally, so masked lanes' rows
    must return to the zeros a fresh prompt starts from.  ``mask`` is
    ``(B,)`` bool; attention-only archs pass through untouched.
    """
    plan = layer_plan(cfg)

    def zero(c: dict, stacked: bool) -> dict:
        def z(x):
            m = mask[None, :] if stacked else mask  # body leaves: (n_body, B, ...)
            mm = m.reshape(m.shape + (1,) * (x.ndim - m.ndim))
            return jnp.where(mm, jnp.zeros_like(x), x)

        return {k: z(v) for k, v in c.items()}

    def recurrent(kind: str) -> bool:
        return _block_mixer_mlp(kind, cfg)[0] in ("ssm", "rec")

    out = dict(cache)
    for i, kind in enumerate(plan.head):
        if recurrent(kind):
            out[f"head_{i}"] = zero(cache[f"head_{i}"], False)
    if plan.n_body and any(recurrent(k) for k in plan.period):
        body = dict(cache["body"])
        for j, kind in enumerate(plan.period):
            if recurrent(kind):
                body[f"sb_{j}"] = zero(cache["body"][f"sb_{j}"], True)
        out["body"] = body
    for i, kind in enumerate(plan.tail):
        if recurrent(kind):
            out[f"tail_{i}"] = zero(cache[f"tail_{i}"], False)
    return out


def _attn_chunk(x, p, cfg: ArchConfig, c: dict, lanes, starts, lengths,
                layout, tables, chunk: int):
    """One prompt chunk per chunking lane, batched: row ``r`` writes K/V at
    ``starts[r]..starts[r]+lengths[r]-1`` of lane ``lanes[r]`` and attends
    its queries over that lane's whole cached prefix.

    x: (L, C, d).  Non-windowed attention reads the append-only full view;
    sliding-window layers on a paged layout read the modular-table view —
    the last ``win + C - 1`` positions ending at the chunk's final token
    (everything a ``win``-wide window can reach), with the below-zero left
    edge masked via ``kv_valid_from``.  Windowed *slab* caches stay gated
    off chunking by the engine."""
    b, csz, _ = x.shape
    h, kv, hd = cfg.n_heads, cfg.n_kv, cfg.hd
    q = L.matmul(x, p["wq"])
    k = L.matmul(x, p["wk"])
    v = L.matmul(x, p["wv"])
    if cfg.qkv_bias:
        q, k, v = q + p["bias_q"], k + p["bias_k"], v + p["bias_v"]
    q = q.reshape(b, csz, h, hd)
    k = k.reshape(b, csz, kv, hd)
    v = v.reshape(b, csz, kv, hd)
    posb = starts[:, None] + jnp.arange(csz)[None, :]  # (L, C)
    if cfg.rope == "rope":
        q = L.apply_rope(q, posb, cfg.rope_theta)
        k = L.apply_rope(k, posb, cfg.rope_theta)
    elif cfg.rope == "mrope":
        p3 = jnp.broadcast_to(posb[..., None], (b, csz, 3))
        q = L.apply_mrope(q, p3, theta=cfg.rope_theta)
        k = L.apply_mrope(k, p3, theta=cfg.rope_theta)
    windowed = isinstance(layout, C.PagedLayout) and layout._windowed(
        cfg.local_window
    )
    new_c = layout.attn_write_chunk(
        c, k, v, lanes, starts, lengths, tables,
        window=cfg.local_window if windowed else None,
    )
    # pad rows (i >= length, or a sentinel lane) attend garbage — discarded
    # by the caller, which reads logits only at row length-1 (and only on
    # the final chunk)
    if windowed:
        win = min(layout.max_len, cfg.local_window)
        k_view, v_view = layout.attn_chunk_view_win(
            new_c, lanes, starts, csz, cfg.local_window, tables
        )
        out = L.chunked_attention(
            q, k_view, v_view, causal=True, window=win,
            q_offset=win - 1,  # q[0] sits at view slot S_v - C = win - 1
            kv_valid_from=jnp.maximum(0, win - 1 - starts),
            chunk=chunk,
        )
    else:
        k_view, v_view = layout.attn_chunk_view(new_c, lanes, tables)
        out = L.chunked_attention(
            q, k_view, v_view, causal=True, q_offset=starts, chunk=chunk
        )
    out = L.matmul(out.reshape(b, csz, h * hd), p["wo"])
    if cfg.o_bias:
        out = out + p["bias_o"]
    return out, new_c


def _block_chunk(x, p, kind: str, cfg: ArchConfig, c, lanes, starts, lengths,
                 layout, tables, chunk: int):
    mixer, mlp = _block_mixer_mlp(kind, cfg)
    if mixer not in ("attn", "mla"):
        raise NotImplementedError(
            "chunked prefill requires attention-family mixers (recurrent "
            "state cannot resume mid-prompt); the engine gates this"
        )
    h = _apply_norm(cfg, p["pre"], x)
    if mixer == "attn":
        mix_out, c = _attn_chunk(
            h, p["attn"], cfg, c, lanes, starts, lengths, layout, tables, chunk
        )
    else:
        mix_out, c = MLA.mla_chunk(
            h, p["attn"], cfg.n_heads, cfg.mla, c, lanes, starts, lengths,
            cfg.rope_theta, layout=layout, tables=tables, chunk=chunk,
        )
    x = x + mix_out
    if mlp != "none":
        h2 = _apply_norm(cfg, p["post"], x)
        if mlp == "moe":
            mo, _ = MOE.moe_mlp(h2, p["moe"], cfg.moe)
        elif cfg.mlp == "swiglu":
            mo = L.swiglu_mlp(h2, p["mlp"])
        else:
            mo = L.gelu_mlp(h2, p["mlp"])
        x = x + mo
    return x, c


def prefill_chunk(
    params: dict, cfg: ArchConfig, tokens: jnp.ndarray, cache: dict,
    lanes, starts, lengths, layout=None, *, chunk: int = 512,
    all_logits: bool = False,
) -> tuple[jnp.ndarray, dict]:
    """Process one fixed-size prompt chunk of every chunking lane against
    the live serving cache: tokens (L, C) int32 (row ``r`` valid below
    ``lengths[r]``) → (logits (L, V) at each row's last valid position,
    new cache).

    This is the incremental counterpart of ``prefill``: each chunk's K/V
    (or MLA latents) are scattered into its lane's cache slots at
    positions ``starts[r]..starts[r]+lengths[r]-1`` and its queries attend
    through the cached prefix, so long prompts are absorbed across several
    small dispatches that the engine interleaves with decode dispatches
    instead of one monolithic head-of-line-blocking forward — and **one**
    dispatch absorbs a chunk of *every* currently-chunking lane (rows with
    a sentinel lane index are padding and write nothing).  The returned
    logits matter only on each lane's final chunk (they seed its first
    sampled token).  Attention-family archs only; the cache's ``len`` for
    ``lanes[r]`` advances to ``starts[r] + lengths[r]``.

    ``all_logits=True`` is the speculative-verify seam: the unembed runs
    over the *whole* chunk and logits come back as ``(L, C, V)`` — row
    ``r`` slot ``j`` scores position ``starts[r] + j``, i.e. the verifier
    distribution for the token *after* ``tokens[r, j]``.  Pad slots
    (``j >= lengths[r]``) are garbage and must be masked by the caller.
    """
    if layout is None:
        layout = C.SlabLayout()
    plan = layer_plan(cfg)
    tables = cache.get("tables")
    x = params["embed"]["tok_embed"][tokens]  # (L, C, d)
    new_cache: dict = {
        "len": cache["len"].at[lanes].set(
            (starts + lengths).astype(cache["len"].dtype), mode="drop"
        )
    }
    if tables is not None:
        new_cache["tables"] = tables

    for i, kind in enumerate(plan.head):
        x, c = _block_chunk(
            x, params[f"head_{i}"], kind, cfg, cache[f"head_{i}"], lanes,
            starts, lengths, layout, tables, chunk,
        )
        new_cache[f"head_{i}"] = c

    if plan.n_body:
        def scan_body(x, pc):
            p_sb, c_sb = pc
            cs = {}
            for j, kind in enumerate(plan.period):
                x, cj = _block_chunk(
                    x, p_sb[f"sb_{j}"], kind, cfg, c_sb[f"sb_{j}"], lanes,
                    starts, lengths, layout, tables, chunk,
                )
                cs[f"sb_{j}"] = cj
            return x, cs

        x, body_cache = jax.lax.scan(scan_body, x, (params["body"], cache["body"]))
        new_cache["body"] = body_cache

    for i, kind in enumerate(plan.tail):
        x, c = _block_chunk(
            x, params[f"tail_{i}"], kind, cfg, cache[f"tail_{i}"], lanes,
            starts, lengths, layout, tables, chunk,
        )
        new_cache[f"tail_{i}"] = c

    if all_logits:
        # speculative verify: score every chunk slot in one unembed —
        # slot j of row r is the verifier distribution at starts[r] + j
        xn = _apply_norm(cfg, params["final"], x)
        if cfg.tie_embeddings:
            logits_all = xn @ params["embed"]["tok_embed"].T
        else:
            logits_all = L.matmul(xn, params["unembed"]["out_embed"])
        return logits_all, new_cache
    # logits only at each row's last valid position — the unembed matmul
    # runs on one token per row, not the whole chunk
    idx = jnp.clip(lengths - 1, 0, tokens.shape[1] - 1)
    x_last = jnp.take_along_axis(x, idx[:, None, None], axis=1)  # (L, 1, d)
    x_last = _apply_norm(cfg, params["final"], x_last)
    if cfg.tie_embeddings:
        logits = x_last @ params["embed"]["tok_embed"].T
    else:
        logits = L.matmul(x_last, params["unembed"]["out_embed"])
    return logits[:, 0, :], new_cache


def _attn_decode(x, p, cfg: ArchConfig, c: dict, pos, layout, tables):
    """x: (B,1,d). pos: (B,) positions of the new token."""
    b = x.shape[0]
    h, kv, hd = cfg.n_heads, cfg.n_kv, cfg.hd
    q = L.matmul(x, p["wq"])
    k = L.matmul(x, p["wk"])
    v = L.matmul(x, p["wv"])
    if cfg.qkv_bias:
        q, k, v = q + p["bias_q"], k + p["bias_k"], v + p["bias_v"]
    q = q.reshape(b, 1, h, hd)
    k = k.reshape(b, 1, kv, hd)
    v = v.reshape(b, 1, kv, hd)
    posb = jnp.reshape(pos, (b, 1))
    if cfg.rope == "rope":
        q = L.apply_rope(q, posb, cfg.rope_theta)
        k = L.apply_rope(k, posb, cfg.rope_theta)
    elif cfg.rope == "mrope":
        p3 = jnp.broadcast_to(posb[..., None], (b, 1, 3))
        q = L.apply_mrope(q, p3, theta=cfg.rope_theta)
        k = L.apply_mrope(k, p3, theta=cfg.rope_theta)

    if isinstance(layout, C.PagedLayout) and dispatch.uses_kernel(
        "paged_attn", b=b, n_slots=tables[layout.table_key(cfg.local_window)].shape[1],
        page_size=layout.page_size, num_pages=layout.num_pages,
        shards=layout.shards,
    ):
        # fast path: scatter the new token into its page, then attend
        # through the page table directly — no contiguous (B, S, ...) K/V
        # view is gathered per step (kernels/paged_attn.py)
        new_c = layout.attn_write(
            c, k[:, 0], v[:, 0], pos, tables, cfg.local_window
        )
        win = layout.view_window(cfg.local_window)
        qg = q[:, 0].reshape(b, kv, h // kv, hd)
        out = dispatch.paged_attn(
            qg, new_c["k"], new_c["v"],
            tables[layout.table_key(cfg.local_window)], pos + 1,
            scale=hd ** -0.5, window=win,
            win_slots=layout.pages_win if win else 0,
            shards=layout.shards,
            k_scale=new_c.get("k_scale"), v_scale=new_c.get("v_scale"),
        )
        out = out.reshape(b, 1, h, hd)
    else:
        # reference path: write the new token, read the logical
        # (oldest→newest) view back — through the slab or the page table,
        # the decode math is the same
        k_view, v_view, new_c = layout.attn_rw(
            c, k[:, 0], v[:, 0], pos, tables, cfg.local_window
        )
        s_view = k_view.shape[1]
        out = L.decode_attention(
            q, k_view, v_view, jnp.minimum(pos, s_view - 1) + 1
        )
    out = L.matmul(out.reshape(b, 1, h * hd), p["wo"])
    if cfg.o_bias:
        out = out + p["bias_o"]
    return out, new_c


def _block_decode(x, p, kind: str, cfg: ArchConfig, c, pos, layout, tables):
    mixer, mlp = _block_mixer_mlp(kind, cfg)
    h = _apply_norm(cfg, p["pre"], x)
    if mixer == "attn":
        mix_out, c = _attn_decode(h, p["attn"], cfg, c, pos, layout, tables)
    elif mixer == "mla":
        mix_out, c = MLA.mla_decode(
            h, p["attn"], cfg.n_heads, cfg.mla, c, pos, cfg.rope_theta,
            layout=layout, tables=tables,
        )
    elif mixer == "ssm":
        mix_out, st, cv = SSM.ssm_decode_step(
            h, p["mixer"], cfg.d_model, cfg.ssm, c["state"], c["conv"]
        )
        c = {"state": st, "conv": cv}
    elif mixer == "rec":
        mix_out, st, cv = REC.rglru_decode_step(
            h, p["mixer"], cfg.rglru, c["state"], c["conv"]
        )
        c = {"state": st, "conv": cv}
    x = x + mix_out
    if mlp != "none":
        h2 = _apply_norm(cfg, p["post"], x)
        if mlp == "moe":
            mo, _ = MOE.moe_mlp(h2, p["moe"], cfg.moe)
        elif cfg.mlp == "swiglu":
            mo = L.swiglu_mlp(h2, p["mlp"])
        else:
            mo = L.gelu_mlp(h2, p["mlp"])
        x = x + mo
    return x, c


def decode_step(
    params: dict, cfg: ArchConfig, tokens: jnp.ndarray, cache: dict, layout=None
) -> tuple[jnp.ndarray, dict]:
    """One serving step: tokens (B,) int32 -> (logits (B,V), new cache).

    ``layout`` selects the cache storage geometry (slab default / paged);
    a paged cache carries its page tables in ``cache["tables"]``, which
    pass through unchanged (the host-side pool manager owns them).
    """
    if layout is None:
        layout = C.SlabLayout()
    plan = layer_plan(cfg)
    pos = cache["len"]  # (B,)
    tables = cache.get("tables")
    x = params["embed"]["tok_embed"][tokens][:, None, :]  # (B,1,d)
    new_cache: dict = {"len": cache["len"] + 1}
    if tables is not None:
        new_cache["tables"] = tables

    for i, kind in enumerate(plan.head):
        x, c = _block_decode(
            x, params[f"head_{i}"], kind, cfg, cache[f"head_{i}"], pos,
            layout, tables,
        )
        new_cache[f"head_{i}"] = c

    if plan.n_body:
        def scan_body(x, pc):
            p_sb, c_sb = pc
            cs = {}
            for j, kind in enumerate(plan.period):
                x, cj = _block_decode(
                    x, p_sb[f"sb_{j}"], kind, cfg, c_sb[f"sb_{j}"], pos,
                    layout, tables,
                )
                cs[f"sb_{j}"] = cj
            return x, cs

        x, body_cache = jax.lax.scan(scan_body, x, (params["body"], cache["body"]))
        new_cache["body"] = body_cache

    for i, kind in enumerate(plan.tail):
        x, c = _block_decode(
            x, params[f"tail_{i}"], kind, cfg, cache[f"tail_{i}"], pos,
            layout, tables,
        )
        new_cache[f"tail_{i}"] = c

    x = _apply_norm(cfg, params["final"], x)
    if cfg.tie_embeddings:
        logits = x @ params["embed"]["tok_embed"].T  # embeddings stay dense
    else:
        logits = L.matmul(x, params["unembed"]["out_embed"])
    return logits[:, 0, :], new_cache


def prefill(
    params: dict, cfg: ArchConfig, batch: dict, max_len: int, *, chunk: int = 512
) -> tuple[jnp.ndarray, dict]:
    """Process a prompt; build the decode cache. Returns (last logits, cache)."""
    logits, _, caches = forward(
        params, cfg, batch, chunk=chunk, remat=False, want_cache=True
    )
    if "tokens" in batch:
        b, s = batch["tokens"].shape
    else:
        b, s = batch["embeds"].shape[:2]
    cache = init_cache(cfg, b, max_len)
    cache["len"] = jnp.full((b,), s, jnp.int32)

    def fill(kind: str, c, produced):
        mixer, _ = _block_mixer_mlp(kind, cfg)
        if mixer == "attn":
            k, v = produced
            sc = c["k"].shape[1]
            if sc >= s:
                return {
                    "k": jax.lax.dynamic_update_slice(c["k"], k, (0, 0, 0, 0)),
                    "v": jax.lax.dynamic_update_slice(c["v"], v, (0, 0, 0, 0)),
                }
            return {"k": k[:, -sc:], "v": v[:, -sc:]}  # window cache
        if mixer == "mla":
            ckv, krope = produced
            return {
                "ckv": jax.lax.dynamic_update_slice(c["ckv"], ckv, (0, 0, 0)),
                "krope": jax.lax.dynamic_update_slice(c["krope"], krope, (0, 0, 0)),
            }
        if mixer == "ssm":
            st, tail = produced
            # short prompts: left-pad the conv tail with the cache's zeros
            w1 = c["conv"].shape[1]
            tail = tail.astype(c["conv"].dtype)
            if tail.shape[1] < w1:
                tail = jnp.concatenate(
                    [c["conv"][:, : w1 - tail.shape[1]], tail], axis=1
                )
            return {"state": st, "conv": tail}
        if mixer == "rec":
            st, cv = produced
            return {"state": st, "conv": cv.astype(c["conv"].dtype)}
        raise AssertionError(mixer)

    plan = layer_plan(cfg)
    for i, kind in enumerate(plan.head):
        cache[f"head_{i}"] = fill(kind, cache[f"head_{i}"], caches[f"head_{i}"])
    if plan.n_body:
        # vmapped fill over the body stack
        def fill_sb(c_sb, pr_sb):
            return {
                f"sb_{j}": fill(kind, c_sb[f"sb_{j}"], pr_sb[f"sb_{j}"])
                for j, kind in enumerate(plan.period)
            }

        cache["body"] = jax.vmap(fill_sb)(cache["body"], caches["body"])
    for i, kind in enumerate(plan.tail):
        cache[f"tail_{i}"] = fill(kind, cache[f"tail_{i}"], caches[f"tail_{i}"])
    return logits[:, -1, :], cache


# ---------------------------------------------------------------------------
# analytics
# ---------------------------------------------------------------------------


def param_count(cfg: ArchConfig) -> int:
    import math

    shapes = jax.eval_shape(lambda k: init_params(cfg, k), jax.random.PRNGKey(0))
    return sum(
        math.prod(x.shape) for x in jax.tree_util.tree_leaves(shapes)
    )


def active_param_count(cfg: ArchConfig) -> int:
    """Active params per token (MoE: only top-k experts count)."""
    total = param_count(cfg)
    if cfg.moe is None:
        return total
    e, k = cfg.moe.n_experts, cfg.moe.top_k
    expert_p = 3 * cfg.d_model * cfg.moe.d_ff_expert
    plan = layer_plan(cfg)
    n_moe = sum(
        1
        for kind in (
            list(plan.head)
            + list(plan.period) * plan.n_body
            + list(plan.tail)
        )
        if _block_mixer_mlp(kind, cfg)[1] == "moe"
    )
    return total - n_moe * (e - k) * expert_p


def model_flops_per_token(cfg: ArchConfig, seq_len: int) -> float:
    """MODEL_FLOPS/token = 6·N_active (+ attention quadratic term)."""
    n_active = active_param_count(cfg)
    flops = 6.0 * n_active
    # causal attention: 12 * L_attn * H * hd * S/2 per token (fwd+bwd ~ 3x fwd)
    plan = layer_plan(cfg)
    kinds = list(plan.head) + list(plan.period) * plan.n_body + list(plan.tail)
    n_attn = sum(1 for k in kinds if _block_mixer_mlp(k, cfg)[0] in ("attn", "mla"))
    w = cfg.local_window
    eff_s = seq_len if w is None else min(w, seq_len)
    flops += 6.0 * n_attn * cfg.n_heads * cfg.hd * (eff_s / 2) * 2
    return flops


class TransformerLM:
    """Thin OO wrapper tying an ArchConfig to the functional API."""

    def __init__(self, cfg: ArchConfig):
        self.cfg = cfg

    def init(self, key):
        return init_params(self.cfg, key)

    def loss(self, params, batch, **kw):
        return loss_fn(params, self.cfg, batch, **kw)

    def forward(self, params, batch, **kw):
        return forward(params, self.cfg, batch, **kw)

    def prefill(self, params, batch, max_len, **kw):
        return prefill(params, self.cfg, batch, max_len, **kw)

    def decode_step(self, params, tokens, cache, layout=None):
        return decode_step(params, self.cfg, tokens, cache, layout)

    def prefill_chunk(self, params, tokens, cache, lanes, starts, lengths,
                      layout=None, **kw):
        return prefill_chunk(
            params, self.cfg, tokens, cache, lanes, starts, lengths, layout, **kw
        )

    def init_cache(self, batch_size, max_len, dtype=None, layout=None):
        return init_cache(self.cfg, batch_size, max_len, dtype, layout)

    def write_prefill(self, cache, produced, lanes, lens, layout=None):
        return write_prefill(cache, self.cfg, produced, lanes, lens, layout)

    def reset_lanes(self, cache, mask):
        return reset_lanes(self.cfg, cache, mask)
