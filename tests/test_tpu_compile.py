"""Compile-only checks of the main-path Pallas kernels for a TPU v5e.

The TPU compiler is installed next to the CPU backend, so each kernel is
lowered and compiled here for a DESCRIBED (not attached) v5e chip at the
flat-buffer width of one transformer-wmt node. This catches what the
Pallas interpreter cannot: casts and shifts the Mosaic compiler refuses,
unaligned tiles, VMEM overruns. Nothing runs, so nothing here is a
result or a time.

The topology is described inside a module-scoped fixture only (never at
import): only one process may hold the TPU library, and under a
multi-worker pytest run every worker imports this file.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import decode_avg, quantize_mod, sgd_fused_update

BLOCK = 256


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def n_padded():
    """Flat-buffer width of one transformer-wmt node (published widths)."""
    from repro.configs import get_config
    from repro.core.bucket import build_flat_layout
    from repro.models import init_params
    cfg = get_config("transformer-wmt")
    probe = jax.eval_shape(lambda k: init_params(k, cfg),
                           jax.random.PRNGKey(0))
    return build_flat_layout(probe, block=BLOCK).n_padded


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile_text(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


CODECS = [(8, False), (4, True), (16, False), (2, False)]
CODEC_IDS = ["q8", "q4-pack4", "q16", "q2"]


@pytest.mark.parametrize("bits,pack4", CODECS, ids=CODEC_IDS)
def test_quantize_compiles(one_chip, n_padded, bits, pack4):
    x = _spec((n_padded,), jnp.float32, one_chip)

    def enc(x, ref, u):
        q, s, _ = quantize_mod(x, ref, u, block=BLOCK, bits=bits,
                               pack4=pack4, backend="pallas")
        return q, s
    assert "tpu_custom_call" in _compile_text(enc, x, x, x)


@pytest.mark.parametrize("matched", [False, True], ids=["plain", "matched"])
@pytest.mark.parametrize("bits,pack4", CODECS, ids=CODEC_IDS)
def test_decode_avg_compiles(one_chip, n_padded, bits, pack4, matched):
    rows = n_padded // BLOCK
    q = _spec((rows, BLOCK // 2 if pack4 else BLOCK),
              jnp.uint8 if bits <= 8 else jnp.uint16, one_chip)
    s = _spec((rows, 1), jnp.float32, one_chip)
    y = _spec((n_padded,), jnp.float32, one_chip)
    args = (q, s, y)
    if matched:
        args += (_spec((rows,), jnp.bool_, one_chip),)

    def dec(q, s, y, m=None):
        return decode_avg(q, s, y, block=BLOCK, bits=bits, matched=m,
                          pack4=pack4, backend="pallas")
    assert "tpu_custom_call" in _compile_text(dec, *args)


@pytest.mark.parametrize("pdtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_sgd_update_compiles(one_chip, n_padded, pdtype):
    p = _spec((n_padded,), pdtype, one_chip)
    m = _spec((n_padded,), jnp.float32, one_chip)
    lr = _spec((), jnp.float32, one_chip)

    def upd(p, g, m, lr):
        return sgd_fused_update(p, g, m, lr=lr, mu=0.9, wd=0.01,
                                backend="pallas")
    assert "tpu_custom_call" in _compile_text(upd, p, p, m, lr)


# The whole-buffer paths around the kernels, at two transformer-wmt nodes.
# Reshaping the node-stacked [n_nodes, n_padded] buffer (or a layer-stacked
# leaf) straight into kernel rows once compiled to ~1 GB of TPU code and
# minutes of compile per superstep; these guard the split reshapes.
CODE_BYTES_MAX = 32 << 20
N_NODES = 2


@pytest.fixture(scope="module")
def wmt_nodes(one_chip):
    from repro.configs import get_config
    from repro.models import init_params
    cfg = get_config("transformer-wmt")
    probe = jax.eval_shape(lambda k: init_params(k, cfg),
                           jax.random.PRNGKey(0))
    return jax.tree.map(
        lambda x: _spec((N_NODES,) + x.shape, x.dtype, one_chip), probe)


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def test_flat_gossip_compiles_compact(one_chip, wmt_nodes):
    from repro.core import bucket as B
    from repro.quant.codecs import make_codec
    from repro.quant.schemes import ModularQuantConfig
    codec = make_codec(None, ModularQuantConfig())
    layout = B.build_layout(wmt_nodes, block=codec.block)

    def gossip(params, prev, perm, key):
        out, _ = B.gossip_flat_coded(
            codec, B.pack(layout, params), B.pack(layout, prev), perm,
            perm != jnp.arange(N_NODES), key, backend="pallas")
        return B.unpack(layout, out)
    c = _compile(gossip, wmt_nodes, wmt_nodes,
                 _spec((N_NODES,), jnp.int32, one_chip),
                 _spec((2,), jnp.uint32, one_chip))
    assert c.as_text().count('custom_call_target="tpu_custom_call"') == 2
    assert c.memory_analysis().generated_code_size_in_bytes < CODE_BYTES_MAX


def test_local_steps_compile_compact(one_chip, wmt_nodes, monkeypatch):
    """Grad + fused SGD of each node, vmapped: the grads' layouts come from
    the backward pass, and packing them through a 1-D vector is what grew
    the program."""
    import repro.kernels.ops as ops
    from repro.configs import get_config
    from repro.core.exchange import make_local_steps
    from repro.models import loss_fn
    from repro.optim import make_optimizer
    # a described chip is not the default backend: steer the platform
    # default to the Pallas kernel for this compile only
    monkeypatch.setattr(ops, "resolve_backend", lambda b: b or "pallas")
    cfg = get_config("transformer-wmt")
    opt = make_optimizer("sgd", lr=0.05, momentum=0.9)
    steps = jax.vmap(make_local_steps(lambda p, mb: loss_fn(cfg, p, mb),
                                      opt.update, 1),
                     in_axes=(0, 0, 0, 0, None))
    mom = {"m": jax.tree.map(
        lambda x: _spec(x.shape, jnp.float32, one_chip), wmt_nodes)}
    tok = _spec((N_NODES, 1, 4, 128), jnp.int32, one_chip)
    c = _compile(lambda p, m, b, h: steps(p, m, b, h, 0.05), wmt_nodes, mom,
                 {"tokens": tok, "targets": tok},
                 _spec((N_NODES,), jnp.int32, one_chip))
    assert 'custom_call_target="tpu_custom_call"' in c.as_text()
    assert c.memory_analysis().generated_code_size_in_bytes < CODE_BYTES_MAX


# The paged serving engine's two per-step programs at a small paged
# configuration with the real head width (128) and a page of 16 rows, the
# chunk program at every bucket width: the new KV rows are written into
# the donated page pools in place, so no pool-sized buffer is copied,
# selected or contracted, and a narrower chunk computes less.
@pytest.mark.parametrize("n_layers,pattern", [(2, 1), (5, 2)],
                         ids=["blocks", "blocks+tail"])
def test_paged_engine_writes_pools_in_place(one_chip, n_layers, pattern):
    import dataclasses
    import math
    import re
    from repro.configs.base import get_config, reduced
    from repro.models import init_params
    from repro.serve import EngineConfig, ServeEngine
    from repro.serve import paged as P
    cfg = reduced(get_config("olmo-1b"), n_layers=n_layers, d_model=256)
    cfg = dataclasses.replace(cfg, pattern=cfg.pattern * pattern,
                              dtype="bfloat16", n_heads=8, n_kv_heads=8,
                              head_dim=128)
    # 19 pages: no other tensor of the programs has a pool's element count
    ecfg = EngineConfig(max_slots=4, prompt_len=48, max_new_tokens=16,
                        paged=True, page_size=16, n_pages=19,
                        prefill_chunk=16)
    eng = ServeEngine(cfg, ecfg)
    pools = jax.tree.leaves(eng._pools)
    assert {p.ndim for p in pools} == ({5} if pattern == 1 else {4, 5})
    pool_bytes = P.tree_num_bytes(eng._pools)
    shapes = {"[" + ",".join(map(str, p.shape)) + "]" for p in pools}
    sizes = {p.size for p in pools}

    def specs(tree):
        return jax.tree.map(lambda x: _spec(x.shape, x.dtype, one_chip), tree)
    S, T = ecfg.max_slots, ecfg.prefill_chunk
    params = specs(jax.eval_shape(
        lambda: init_params(jax.random.PRNGKey(0), cfg)))
    key = specs(jax.eval_shape(lambda: jax.random.PRNGKey(0)))
    i32 = lambda *s: _spec(s, jnp.int32, one_chip)
    flag = lambda w: _spec((w,), jnp.bool_, one_chip)
    head = (params, specs(eng._caches), specs(eng._pools), i32(S, 1))
    assert eng._chunk_widths == [1, 2, 4]
    programs = [(eng._decode, (flag(S), key))] + [
        (eng._chunk_fn, (i32(w, T), i32(w), flag(w), flag(w), i32(w), key))
        for w in eng._chunk_widths]
    flops = {}
    for fn, rest in programs:
        c = fn.lower(*head, *rest).compile()
        if fn is eng._chunk_fn:
            flops[rest[0].shape[0]] = c.cost_analysis()["flops"]
        assert c.memory_analysis().alias_size_in_bytes >= pool_bytes
        for line in c.as_text().splitlines():
            m = re.match(r"\s*(?:ROOT )?%\S+ = \w+(\[[\d,]*\])\S* "
                         r"(copy|select|convolution)\(", line)
            if not m:
                continue
            shape, op = m.groups()
            assert shape not in shapes, f"pool-sized {op}: {line[:160]}"
            n = math.prod(int(d) for d in shape[1:-1].split(",") if d)
            assert op != "convolution" or n not in sizes, line[:160]
    # the chunk's matrix products scale with the bucket's width
    assert 0 < flops[1] <= 0.4 * flops[4], flops
