import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"
# ^ MUST precede any other import (jax locks device count on first init);
# the 512 placeholder devices are host devices, never the chip.
"""Multi-pod dry-run: lower + compile every (arch × input shape × mesh)
against the production v5e mesh with 512 placeholder host devices, and emit
the roofline terms (deliverables e and g).

  PYTHONPATH=src python -m repro.launch.dryrun --arch gemma3-4b \
      --shape train_4k --mesh single --gossip gather --out results/
"""
import argparse          # noqa: E402
import json              # noqa: E402
import time              # noqa: E402
from functools import partial  # noqa: E402

import jax               # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np       # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.configs import INPUT_SHAPES, get_config  # noqa: E402
from repro.core.swarm import SwarmConfig, SwarmState, make_swarm_step  # noqa: E402
from repro.launch import specs as S  # noqa: E402
from repro.launch.mesh import make_production_mesh  # noqa: E402
from repro.models import forward, init_cache, loss_fn as model_loss  # noqa: E402
from repro.models.layers import ParamInfo, is_info  # noqa: E402
from repro.models.unroll import set_unroll  # noqa: E402
from repro.models.transformer import logits_head, param_template  # noqa: E402
from repro.optim import make_optimizer  # noqa: E402
from repro.roofline.analysis import analyze_compiled, model_flops  # noqa: E402

DEFAULT_H = 2


def stacked_param_sds(cfg, n_nodes):
    dt = jnp.dtype(cfg.dtype)
    return jax.tree.map(
        lambda i: jax.ShapeDtypeStruct((n_nodes,) + i.shape, dt),
        param_template(cfg), is_leaf=is_info)


def param_sds(cfg):
    dt = jnp.dtype(cfg.dtype)
    return jax.tree.map(lambda i: jax.ShapeDtypeStruct(i.shape, dt),
                        param_template(cfg), is_leaf=is_info)


def prepend_spec(spec_tree, part):
    return jax.tree.map(lambda s: P(part, *s),
                        spec_tree, is_leaf=lambda s: isinstance(s, P))


def build_train(cfg, shape, mesh, gossip: str, quantize: bool = False,
                nonblocking: bool = False, H: int = DEFAULT_H,
                ce_anchor: bool = False, moe_c_shard: bool = False,
                overlap: bool = False):
    n_nodes = S.n_nodes_for(cfg, mesh)
    node_axes = S.node_axes_for(cfg, mesh)
    shard = S.make_shard_fn(cfg, mesh, "train", ce_anchor=ce_anchor,
                            moe_c_shard=moe_c_shard)
    opt = make_optimizer("sgd", lr=0.1, momentum=0.9,
                         state_dtype=cfg.opt_state_dtype)
    # one representative static matching: node i <-> i^1
    perm_np = np.asarray([i ^ 1 if (i ^ 1) < n_nodes else i
                          for i in range(n_nodes)], np.int32)
    static_pairs = [(int(perm_np[d]), d) for d in range(n_nodes)
                    if perm_np[d] != d]
    if not static_pairs:
        static_pairs = [(0, 0)]

    pspec_single = S.param_pspec(cfg, mesh, node_stacked=False)
    node_part = node_axes if node_axes else None
    pspec = prepend_spec(pspec_single, node_part)

    scfg = SwarmConfig(n_nodes=n_nodes, H=H, quantize=quantize,
                       nonblocking=nonblocking or overlap, overlap=overlap,
                       gossip_impl=gossip, track_potential=False)
    lf = lambda p, mb: model_loss(cfg, p, mb, shard=shard)  # noqa: E731
    step = make_swarm_step(scfg, lf, opt.update, lambda s: 0.1, shard=shard,
                           mesh=mesh, param_specs=pspec, node_axes=node_axes,
                           static_pairs=static_pairs)

    psds = stacked_param_sds(cfg, n_nodes)
    mdt = jnp.dtype(cfg.opt_state_dtype)
    msds = {"m": jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, mdt), psds)}
    prev_sds = psds if (quantize or scfg.nonblocking) and not overlap else None
    infl_sds = infl_spec = None
    if overlap:
        # pipelined mode: the comm copy + in-flight payload live packed in
        # SwarmState.inflight (DESIGN.md §Pipeline); BucketLayout works on
        # ShapeDtypeStructs, so the wire shapes come out without an init —
        # the codec's declared WireLayout supplies the wire-group SDS
        from repro.core import bucket as B
        from repro.quant.codecs import make_codec
        codec = make_codec(scfg.codec, scfg.quant)
        lay = B.build_layout(psds, block=codec.block)
        buf = jax.ShapeDtypeStruct((n_nodes, lay.n_padded), jnp.float32)
        infl_sds = {"sbuf": buf}
        infl_spec = {"sbuf": P(node_part, None)}
        if quantize:
            rows = n_nodes * lay.rows_per_node
            infl_sds.update(
                prev=buf, wire=codec.wire_layout().wire_sds(rows))
            infl_spec.update(
                prev=P(node_part, None),
                wire=tuple(P(node_part, None)
                           for _ in infl_sds["wire"]))
    state_sds = SwarmState(psds, msds, prev_sds,
                           jax.ShapeDtypeStruct((), jnp.int32), infl_sds)
    state_spec = SwarmState(pspec, {"m": pspec},
                            pspec if prev_sds is not None else None, P(),
                            infl_spec)

    batch_specs = S.train_input_specs(cfg, shape, mesh, H)
    batch_sds = {k: v[0] for k, v in batch_specs.items()}
    batch_spec = {k: v[1] for k, v in batch_specs.items()}
    perm_sds = jax.ShapeDtypeStruct((n_nodes,), jnp.int32)
    h_sds = jax.ShapeDtypeStruct((n_nodes,), jnp.int32)
    rng_sds = jax.ShapeDtypeStruct((2,), jnp.uint32)

    in_shardings = (S.named(mesh, state_spec),
                    S.named(mesh, batch_spec),
                    NamedSharding(mesh, P()), NamedSharding(mesh, P()),
                    NamedSharding(mesh, P()))
    jitted = jax.jit(step, in_shardings=in_shardings)
    args = (state_sds, batch_sds, perm_sds, h_sds, rng_sds)
    return jitted, args


def build_serve(cfg, shape, mesh, cache_layout: str = "headdim"):
    kv_seq_axis = None
    if cache_layout == "seqshard" and \
            cfg.n_kv_heads % mesh.shape["model"] != 0:
        kv_seq_axis = "model"
    elif shape.global_batch == 1 and not cfg.big_model:
        kv_seq_axis = "data"  # long-context decode: KV seq over data
    shard = S.make_shard_fn(cfg, mesh, "serve", kv_seq_axis=kv_seq_axis)
    pspec = S.param_pspec(cfg, mesh, node_stacked=False, role="serve")
    psds = param_sds(cfg)
    in_specs = S.serve_input_specs(cfg, shape, mesh)

    if shape.kind == "prefill":
        def prefill_step(params, batch):
            hidden, cache, _ = forward(
                cfg, params, batch["tokens"], mode="prefill",
                prefix_embeds=batch.get("prefix_embeds"), shard=shard)
            logits = logits_head(cfg, params, hidden[:, -1:], shard)
            return logits, cache

        batch_sds = {k: v[0] for k, v in in_specs.items()}
        batch_spec = {k: v[1] for k, v in in_specs.items()}
        jitted = jax.jit(prefill_step,
                         in_shardings=(S.named(mesh, pspec),
                                       S.named(mesh, batch_spec)))
        return jitted, (psds, batch_sds)

    # decode: one token, KV cache of seq_len
    cache_sds = jax.eval_shape(
        lambda: init_cache(cfg, shape.global_batch, shape.seq_len))
    cspec = S.cache_pspec(cfg, mesh, shape, layout=cache_layout)

    def serve_step(params, cache, tokens):
        hidden, new_cache, _ = forward(cfg, params, tokens, mode="decode",
                                       cache=cache, shard=shard)
        logits = logits_head(cfg, params, hidden, shard)
        return logits, new_cache

    tok_sds, tok_spec = in_specs["tokens"]
    bax = S.batch_axes_for(cfg, mesh, "serve")
    if shape.global_batch == 1:
        bax = None
    logits_spec = P(bax, None, S.logical_rules(cfg, mesh, "serve")["vocab"])
    jitted = jax.jit(serve_step,
                     in_shardings=(S.named(mesh, pspec),
                                   S.named(mesh, cspec),
                                   NamedSharding(mesh, tok_spec)),
                     out_shardings=(NamedSharding(mesh, logits_spec),
                                    S.named(mesh, cspec)))
    return jitted, (psds, cache_sds, tok_sds)


def run_one(arch: str, shape_name: str, mesh_kind: str, gossip: str = "gather",
            quantize: bool = False, nonblocking: bool = False,
            H: int = DEFAULT_H, flops_mode: str = "unrolled",
            cache_layout: str = "headdim", ce_anchor: bool = False,
            native_partials: bool = False, moe_c_shard: bool = False,
            overlap: bool = False) -> dict:
    """Two-pass dry-run (see EXPERIMENTS.md §Method):

    A) ROLLED lowering -> .compile(): proves the (arch x shape x mesh)
       combination lowers and compiles on the production mesh, yields
       memory_analysis() and the loop-corrected collective bytes from the
       optimized SPMD HLO.
    B) UNROLLED lowering (no compile): exact global FLOPs from
       lowered.cost_analysis() — XLA counts while bodies once, so only the
       unrolled module counts every layer/local-step/chunk.
    Memory term: analytic HBM model (CPU-backend byte counts overcount
    pre-fusion traffic; raw numbers still recorded).
    """
    from repro.roofline import analytic as A
    from repro.roofline.hlo_loops import collective_bytes_corrected
    from repro.launch.mesh import HBM_BW, ICI_LINK_BW, PEAK_FLOPS_BF16
    from repro.models.layers import set_native_partials

    set_native_partials(native_partials)
    cfg = get_config(arch)
    shape = INPUT_SHAPES[shape_name]
    if shape.name == "long_500k" and not cfg.subquadratic:
        return {"arch": arch, "shape": shape_name, "mesh": mesh_kind,
                "skipped": "pure full-attention arch (see DESIGN.md §5)"}
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    n_dev = mesh.size
    n_nodes = S.n_nodes_for(cfg, mesh)

    def build(unroll: bool):
        set_unroll(unroll)
        with mesh:
            if shape.kind == "train":
                jitted, args = build_train(cfg, shape, mesh, gossip, quantize,
                                           nonblocking, H, ce_anchor=ce_anchor,
                                           moe_c_shard=moe_c_shard,
                                           overlap=overlap)
            else:
                jitted, args = build_serve(cfg, shape, mesh,
                                           cache_layout=cache_layout)
            return jitted.lower(*args)

    # Pass A: rolled compile
    t0 = time.time()
    lowered = build(False)
    t_lower = time.time() - t0
    t0 = time.time()
    with mesh:
        compiled = lowered.compile()
    t_compile = time.time() - t0
    ma = compiled.memory_analysis()
    txt = compiled.as_text()
    raw_coll, corr_coll = collective_bytes_corrected(txt)
    f32_share = corr_coll.pop("_f32_share", 0)
    coll_bytes_raw = sum(corr_coll.values())
    # bf16-adjusted: the CPU backend upcasts bf16 dots to f32 before the
    # SPMD partial reductions; on TPU those collectives move bf16, so f32
    # collective bytes are halved for bf16-dtype models (§Method).
    if cfg.dtype == "bfloat16":
        coll_bytes = coll_bytes_raw - f32_share // 2
    else:
        coll_bytes = coll_bytes_raw
    if os.environ.get("REPRO_SAVE_HLO"):
        import gzip
        os.makedirs(os.environ["REPRO_SAVE_HLO"], exist_ok=True)
        tag = f"{arch}__{shape_name}__{mesh_kind}"
        with gzip.open(os.path.join(os.environ["REPRO_SAVE_HLO"],
                                    tag + ".hlo.gz"), "wt") as f:
            f.write(txt)

    # Pass B: unrolled flops (lower only)
    flops_dev = None
    t_unroll = None
    if flops_mode == "unrolled":
        t0 = time.time()
        lo_u = build(True)
        ca = lo_u.cost_analysis()
        flops_dev = float(ca.get("flops", 0.0)) / n_dev
        t_unroll = round(time.time() - t0, 1)
        del lo_u
    set_unroll(False)

    # analytic terms
    if shape.kind == "train":
        an_flops = A.train_flops(cfg, shape, H=H, remat=cfg.remat) / n_dev
        an_bytes = A.train_bytes_full(cfg, shape, n_nodes, H=H,
                                      remat=cfg.remat) / n_dev
    else:
        an_flops = A.serve_flops(cfg, shape) / n_dev
        an_bytes = A.serve_bytes(cfg, shape) / n_dev
    if flops_dev is None:
        flops_dev = an_flops

    mf = model_flops(cfg, shape, shape.kind)
    compute_s = flops_dev / PEAK_FLOPS_BF16
    memory_s = an_bytes / HBM_BW
    collective_s = coll_bytes / ICI_LINK_BW
    terms = {"compute": compute_s, "memory": memory_s,
             "collective": collective_s}
    rolled_ca = compiled.cost_analysis()

    return {
        "arch": arch, "shape": shape_name, "mesh": mesh_kind,
        "kind": shape.kind,
        "gossip": gossip if shape.kind == "train" else None,
        "quantize": quantize, "nonblocking": nonblocking or overlap,
        "overlap": overlap,
        "n_devices": n_dev, "n_nodes": n_nodes,
        "t_lower_s": round(t_lower, 1), "t_compile_s": round(t_compile, 1),
        "t_unroll_lower_s": t_unroll,
        "flops_per_dev": flops_dev,
        "flops_analytic_per_dev": an_flops,
        "bytes_analytic_per_dev": an_bytes,
        "rolled_flops_per_dev": float(rolled_ca.get("flops", 0.0)),
        "rolled_bytes_per_dev": float(rolled_ca.get("bytes accessed", 0.0)),
        "coll_bytes_per_dev": coll_bytes,
        "coll_bytes_unadjusted": coll_bytes_raw,
        "coll_f32_share": f32_share,
        "coll_raw": raw_coll, "coll_corrected": corr_coll,
        "compute_s": compute_s, "memory_s": memory_s,
        "collective_s": collective_s,
        "bottleneck": max(terms, key=terms.get),
        "argument_bytes": int(getattr(ma, "argument_size_in_bytes", 0)),
        "temp_bytes": int(getattr(ma, "temp_size_in_bytes", 0)),
        "model_flops_per_dev": mf / n_dev,
        "useful_ratio": (mf / n_dev) / flops_dev if flops_dev else None,
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True, choices=list(INPUT_SHAPES))
    ap.add_argument("--mesh", default="single", choices=["single", "multi"])
    ap.add_argument("--gossip", default="gather",
                    choices=["gather", "ppermute", "gather_legacy",
                             "ppermute_legacy"],
                    help="*_legacy = per-leaf oracle transports (the default "
                         "modes run the flat-buffer transport; DESIGN.md "
                         "§Perf)")
    ap.add_argument("--quantize", action="store_true")
    ap.add_argument("--nonblocking", action="store_true")
    ap.add_argument("--overlap", action="store_true",
                    help="pipelined non-blocking superstep (implies "
                         "--nonblocking; DESIGN.md §Pipeline)")
    ap.add_argument("--H", type=int, default=DEFAULT_H)
    ap.add_argument("--flops", default="unrolled",
                    choices=["unrolled", "analytic"],
                    help="analytic skips the unrolled lowering pass (used for "
                         "the multi-pod mesh, whose global flops equal the "
                         "single-pod run's)")
    ap.add_argument("--cache-layout", default="headdim",
                    choices=["headdim", "seqshard"])
    ap.add_argument("--ce-anchor", action="store_true")
    ap.add_argument("--moe-c-shard", action="store_true")
    ap.add_argument("--native-partials", action="store_true")
    ap.add_argument("--tag", default="")
    ap.add_argument("--out", default="results/dryrun")
    args = ap.parse_args()
    res = run_one(args.arch, args.shape, args.mesh, args.gossip,
                  args.quantize, args.nonblocking, args.H,
                  flops_mode=args.flops, cache_layout=args.cache_layout,
                  ce_anchor=args.ce_anchor,
                  native_partials=args.native_partials,
                  moe_c_shard=args.moe_c_shard, overlap=args.overlap)
    os.makedirs(args.out, exist_ok=True)
    tag = f"{args.arch}__{args.shape}__{args.mesh}"
    if args.gossip != "gather":
        tag += f"__{args.gossip}"
    if args.quantize:
        tag += "__q8"
    if args.nonblocking:
        tag += "__nb"
    if args.overlap:
        tag += "__ov"
    if args.cache_layout != "headdim":
        tag += f"__{args.cache_layout}"
    if args.ce_anchor:
        tag += "__cea"
    if args.moe_c_shard:
        tag += "__moec"
    if args.native_partials:
        tag += "__np"
    if args.tag:
        tag += "__" + args.tag
    path = os.path.join(args.out, tag + ".json")
    with open(path, "w") as f:
        json.dump(res, f, indent=1, default=str)
    print(json.dumps(res, indent=1, default=str))
    print("wrote", path)


if __name__ == "__main__":
    main()
