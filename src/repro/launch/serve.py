"""Serving launcher: compressed-native continuous-batching decode.

    python -m repro.launch.serve --arch gpt2-paper --batch 4 --prompt-len 16 \
        --gen 32 [--ckpt-dir /tmp/run1] [--dense] [--temperature 0.8 --top-k 40] \
        [--paged --page-size 16 --num-pages 64] [--prefill-buckets 16,32,64] \
        [--steps-per-dispatch 4] [--prefill-chunk 16] [--no-donate]

Loads (or initializes) params, applies the final Π_T mask (Algorithm 1,
line 23-24), exports the N:M-compressed artifact, and hands the *compressed
tree itself* to ``repro.serving.DecodeEngine`` — prefill and every decode
step run directly on ``CompressedTensor`` leaves via the ``nm_spmm`` kernel
path (Pallas on TPU); the dense weights are never rehydrated in HBM.
``--dense`` serves the masked-dense tree instead, as an A/B baseline for
the same engine.  ``--paged`` switches the KV cache from the per-lane slab
to the block-granular paged pool (``--page-size``/``--num-pages``; an
undersized pool preempts-and-requeues instead of truncating), and
``--prefill-buckets`` overrides the static prompt-pad lengths used by
bucketed batched prefill.

Decode-loop knobs: ``--steps-per-dispatch K`` fuses K decode steps into one
on-device scan (the host syncs once per K tokens; greedy streams are
bit-identical across K), ``--prefill-chunk N`` absorbs long prompts in
N-token chunks interleaved with decode dispatches, and ``--no-donate``
disables cache-buffer donation (the copying A/B baseline).

Device-resident scheduler: ``--max-steps-per-dispatch K`` replaces the
fixed-K scan with a run-until-stop ``while_loop`` (the host is consulted
only when a lane freezes or the bound is hit), ``--staged-lanes Q``
pre-stages queued prompts on device so a frozen lane refills and starts
prefilling inside the same dispatch, and ``--async-stream``
double-buffers dispatches so token-block fetches overlap decode.
Streams stay bit-identical to the sync scheduler.

Paged-pool extensions: ``--prefix-cache`` indexes every prefilled prompt's
pages in a radix trie and maps cached prefixes into later requests' tables
(shared refcounted pages, copy-on-write on divergence; ``--shared-prefix N``
gives the synthetic requests a common head so hits actually occur), and
``--kv-int8`` stores KV pages as int8 with per-page-row scales — a ~4x
smaller pool at the same page count, dequantized inside the kernels.

``--mesh data,model`` serves **tensor-parallel**: every engine executable
is jitted with explicit NamedShardings (weights TP via the compressed
pspec seam, KV caches sequence/pages-sharded per ``--kv-shard``), and the
summary grows per-shard HBM bytes and the decode executable's collective
counts.  On CPU, emulate a mesh with
``XLA_FLAGS=--xla_force_host_platform_device_count=8 ... --mesh 2,4``.

Self-speculative decoding: ``--spec-gamma N`` (or ``auto``) drafts N
tokens per lane with the *serving tree* (the compressed N:M artifact, or
the masked-dense tree under ``--dense``) and verifies them in one chunked
pass through the masked-dense weights — both trees fall out of the same
STEP run, no separately trained drafter.  Output streams are exactly the
dense verifier's (longest-prefix accept under greedy, rejection sampling
otherwise); the summary gains ``acceptance_rate``, ``spec_gamma``, and
draft/verify token counts next to ``kernel_route``.  ``auto`` picks γ
from the drafter/verifier byte ratio via the engine's roofline model.
"""
from __future__ import annotations

import argparse
import json

import jax
import jax.numpy as jnp

import repro.core as core
from repro.checkpoint import Checkpointer
from repro.compile_cache import enable_compile_cache
from repro.configs import get_config, list_archs
from repro.models.model import TransformerLM
from repro.serving import DecodeEngine, SamplingParams
from repro.sparse_infer import compress_params, compression_report


def build_serving_state(args) -> tuple:
    """(model, serving_tree, compression_report, sparse_tree) from CLI
    args.  ``sparse_tree`` (the masked-dense Π_T ⊙ w_T weights) doubles
    as the speculative verifier — the two fidelities of one STEP run."""
    cfg = get_config(args.arch, smoke=args.smoke)
    if cfg.frontend != "none":
        raise SystemExit("serve demo targets token-input archs")
    model = TransformerLM(cfg)
    params = model.init(jax.random.PRNGKey(0))
    if args.ckpt_dir:
        # train.py checkpoints store the whole TrainState; NamedTuple fields
        # flatten by field name, so a {"params": ...} skeleton reads just the
        # parameter subtree out of the full-state npz.
        restored = Checkpointer(args.ckpt_dir).restore_latest({"params": params})
        if restored is not None:
            tree, _, step = restored
            params = tree["params"]
            print(f"# restored params from step {step}")

    n, m = (int(x) for x in args.nm.split(":"))
    recipe = core.make_recipe(
        "step", core.SparsityConfig(default=core.NMSparsity(n, m))
    )
    sparse = recipe.export_sparse(params)  # Π_T ⊙ w_T
    comp = compress_params(sparse, recipe.sparsity)
    rep = compression_report(sparse, comp)
    serving_tree = sparse if args.dense else comp
    return model, serving_tree, rep, sparse


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gpt2-paper", choices=list_archs())
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--no-smoke", dest="smoke", action="store_false")
    ap.add_argument("--nm", default="2:4")
    ap.add_argument("--batch", type=int, default=4, help="decode lanes")
    ap.add_argument("--requests", type=int, default=None,
                    help="total requests (default: one per lane)")
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--top-k", type=int, default=0)
    ap.add_argument("--dense", action="store_true",
                    help="serve the masked-dense tree (A/B baseline)")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--paged", action="store_true",
                    help="paged KV-cache pool instead of the per-lane slab")
    ap.add_argument("--page-size", type=int, default=16,
                    help="tokens per KV page (paged layout)")
    ap.add_argument("--num-pages", type=int, default=None,
                    help="total pages in the pool (default: slab-equivalent "
                         "batch*ceil(max_len/page_size))")
    ap.add_argument("--prefill-buckets", default=None,
                    help="comma-separated static prompt-pad lengths for "
                         "bucketed batched prefill (default: powers of two)")
    ap.add_argument("--steps-per-dispatch", type=int, default=1,
                    help="decode steps fused into one on-device scan (the "
                         "host syncs once per K tokens)")
    ap.add_argument("--max-steps-per-dispatch", type=int, default=None,
                    help="device-resident scheduler: run-until-stop decode "
                         "bounded by this many steps per dispatch (replaces "
                         "the fixed-K scan; streams stay bit-identical)")
    ap.add_argument("--staged-lanes", type=int, default=0,
                    help="queued prompts pre-staged on device per cycle so "
                         "frozen lanes refill inside the dispatch (needs "
                         "--max-steps-per-dispatch)")
    ap.add_argument("--async-stream", action="store_true",
                    help="double-buffer decode dispatches: fetch dispatch "
                         "N's tokens while N+1 runs (needs "
                         "--max-steps-per-dispatch)")
    ap.add_argument("--prefill-chunk", type=int, default=None,
                    help="absorb prompts longer than this in fixed-size "
                         "chunks interleaved with decode dispatches "
                         "(attention-family archs only)")
    ap.add_argument("--no-donate", dest="donate", action="store_false",
                    default=True,
                    help="disable cache-buffer donation into the jitted "
                         "decode/prefill (the copying A/B baseline)")
    ap.add_argument("--mesh", default=None,
                    help="serve tensor-parallel on a 'data,model' mesh over "
                         "local devices (e.g. --mesh 2,4 under "
                         "XLA_FLAGS=--xla_force_host_platform_device_count=8); "
                         "weights TP-shard, KV caches sequence/pages-shard, "
                         "and the summary gains per-shard HBM bytes + decode "
                         "collective counts")
    ap.add_argument("--kv-shard", default="seq", choices=("seq", "feature"),
                    help="model-axis dim of the KV caches under --mesh")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="share prompt-prefix KV pages across requests via "
                         "the radix index (paged, attention-family archs); "
                         "hits skip prefilling the cached tokens")
    ap.add_argument("--kv-int8", action="store_true",
                    help="int8 KV pages with per-page-row scales (~4x "
                         "smaller pool at equal page count; paged only)")
    ap.add_argument("--shared-prefix", type=int, default=0,
                    help="give every request the same leading N prompt "
                         "tokens (exercises --prefix-cache; the tail stays "
                         "per-request random)")
    ap.add_argument("--spec-gamma", default=None,
                    help="self-speculative decoding: draft this many tokens "
                         "per lane with the serving tree, verify in one "
                         "chunked pass through the masked-dense weights "
                         "('auto' picks gamma from the byte-ratio roofline; "
                         "attention-family archs, sync scheduler only)")
    args = ap.parse_args(argv)
    spec_gamma = None
    if args.spec_gamma is not None:
        spec_gamma = (
            "auto" if args.spec_gamma == "auto" else int(args.spec_gamma)
        )
    if (args.prefix_cache or args.kv_int8) and not args.paged:
        raise SystemExit("--prefix-cache/--kv-int8 require --paged")
    enable_compile_cache()

    mesh = None
    if args.mesh:
        from repro.launch.mesh import make_local_mesh

        d, m = (int(v) for v in args.mesh.split(","))
        mesh = make_local_mesh(m, data=d)

    model, serving_tree, rep, sparse = build_serving_state(args)
    cfg = model.cfg
    print(json.dumps({"compression": rep}))

    max_len = args.prompt_len + args.gen + 1
    num_pages = args.num_pages
    if args.paged and num_pages is None:
        num_pages = args.batch * (-(-max_len // args.page_size))
    buckets = (
        [int(b) for b in args.prefill_buckets.split(",")]
        if args.prefill_buckets
        else None
    )
    engine = DecodeEngine(
        model,
        serving_tree,
        max_batch=args.batch,
        max_len=max_len,
        seed=0,
        num_pages=num_pages if args.paged else None,
        page_size=args.page_size,
        steps_per_dispatch=args.steps_per_dispatch,
        max_steps_per_dispatch=args.max_steps_per_dispatch,
        staged_lanes=args.staged_lanes,
        async_stream=args.async_stream,
        donate=args.donate,
        prefill_chunk=args.prefill_chunk,
        prefill_buckets=buckets,
        mesh=mesh,
        kv_shard=args.kv_shard,
        prefix_cache=args.prefix_cache,
        kv_quant=args.kv_int8,
        spec_gamma=spec_gamma,
        # masked-dense verifier: with --dense the drafter IS the verifier
        # (acceptance is then 1.0 by construction — a plumbing check)
        verify_params=sparse if spec_gamma is not None else None,
    )
    n_requests = args.batch if args.requests is None else args.requests
    sampling = SamplingParams(
        temperature=args.temperature, top_k=args.top_k, max_new_tokens=args.gen
    )
    shared = []
    if args.shared_prefix:
        n_shared = min(args.shared_prefix, args.prompt_len - 1)
        shared = [
            int(t) for t in jax.random.randint(
                jax.random.PRNGKey(999), (n_shared,), 0, cfg.vocab
            )
        ]
    for r in range(n_requests):
        prompt = jax.random.randint(
            jax.random.PRNGKey(1000 + r),
            (args.prompt_len - len(shared),), 0, cfg.vocab,
        )
        engine.submit(shared + [int(t) for t in prompt], sampling)
    results = engine.run()

    st = engine.stats()
    summary = {
        "arch": cfg.name,
        "compressed": not args.dense,
        "layout": st["layout"],
        "n_requests": len(results),
        "generated_tokens": st["tokens_generated"],
        "tokens_per_s": st["tokens_per_s"],
        "ms_per_decode_step": st["ms_per_decode_step"],
        "ms_per_decode_step_host": st["ms_per_decode_step_host"],
        "host_overhead_frac": st["host_overhead_frac"],
        "decode_steps": st["decode_steps"],
        "dispatches": st["dispatches"],
        "steps_per_dispatch": st["steps_per_dispatch"],
        "scheduler": st["scheduler"],
        "host_syncs": st["host_syncs"],
        "refills": st["refills"],
        "itl_ms_p50": st["itl_ms_p50"],
        "itl_ms_p99": st["itl_ms_p99"],
        "prefill_batches": st["prefill_batches"],
        "prefill_chunks": st["prefill_chunks"],
        "max_concurrency": st["max_concurrency"],
        "preemptions": st["preemptions"],
        "kv_cache_bytes": st["kv_cache_bytes"],
        "hbm_weight_ratio": round(rep["ratio"], 3),
        "mesh": engine.mesh_desc(),
        # which paged-attention implementation decode resolved at trace
        # time ("slab" when no paged kernel is in play); a sharded run
        # compares xla vs shard_map streams on this field
        "kernel_route": engine.kernel_route(),
        # the routes the compressed matmuls took ("none" for --dense)
        "nm_spmm_route": engine.kernel_routes().get("nm_spmm", "none"),
    }
    if spec_gamma is not None:
        # speculative health next to the route: how long the drafts ran,
        # how many survived the dense verifier, and the amortized weight
        # stream each committed token paid for
        for key in (
            "spec_gamma", "spec_rounds", "draft_tokens", "verify_tokens",
            "accepted_draft_tokens", "acceptance_rate",
            "accepted_per_verify", "bytes_per_accepted_token",
        ):
            summary[key] = st[key]
    if args.paged:
        # pool/page-sharing health next to the route: sync costs, window
        # reclamation, and the prefix-cache / copy-on-write counters
        for key in (
            "evicted_pages", "table_full_uploads", "table_row_syncs",
            "table_syncs", "kv_quant", "shared_pages", "cow_copies",
        ):
            summary[key] = st[key]
        for key in (
            "prefix_hits", "prefix_hit_tokens", "prefix_hit_rate",
            "prefix_indexed_pages", "prefix_evictions",
        ):
            if key in st:
                summary[key] = st[key]
    if args.temperature == 0.0:
        # greedy streams are deterministic: recorded so route/mesh A/B
        # runs can assert token-level parity from the summaries alone
        summary["greedy_streams"] = [
            [int(t) for t in results[u].tokens] for u in sorted(results)
        ]
    if mesh is not None:
        sh = engine.sharding_report(include_hlo=True)
        summary["weight_bytes_per_shard"] = sh["weight_bytes_per_shard"]
        summary["cache_bytes_per_shard"] = sh["cache_bytes_per_shard"]
        summary["decode_collective_bytes"] = sh["decode_collective_bytes"]
        summary["decode_collective_total"] = sh["decode_collective_total"]
        # matmul weights only: per-feature vectors replicate by design and
        # would make this column constant nonzero noise
        summary["replicated_weight_leaves"] = sh["replicated_matmul_leaves"]
        # per-shard decode roofline: every shard streams its weight slice
        # each step, and the pages/sequence axis splits the live-KV read
        # over the model axis
        sizes = dict(zip(summary["mesh"]["axes"], summary["mesh"]["shape"]))
        model_shards = int(sizes.get("model", 1))
        summary["model_shards"] = model_shards
        summary["weight_bytes_per_step_per_shard"] = sh["weight_bytes_per_shard"]
        summary["kv_bytes_per_step_per_shard"] = (
            st["kv_bytes_per_step"] / model_shards
        )
        summary["bytes_read_per_step_per_shard"] = (
            sh["weight_bytes_per_shard"] + st["kv_bytes_per_step"] / model_shards
        )
    print(json.dumps({"summary": summary}))
    return summary


if __name__ == "__main__":
    main()
