"""Real-size compiles for a described TPU v5e — no chip needed.

The kernels and steps of the retrieval main path are compiled by the TPU
compiler at the paper's dense shape (capacity 131072, d_pad 16384, block
128, Q=32, B=64, T=8). Interpret mode cannot see what the chip's compiler
refuses (block shapes off the (8, 128) tiling, SMEM overflow, HBM
temporaries); these tests do, a few seconds each.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library at a time, and the worker that runs
this file keeps it until it exits.
"""
import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops

CAP, D_PAD, BLOCK, Q, B, T = 131072, 16384, 128, 32, 64, 8


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler installed
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of these tests
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _spec(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args, **kw):
    return jax.jit(fn).lower(*args, **kw).compile()


@pytest.mark.parametrize("arms,pulls", [(B, T), (CAP, 2)],
                         ids=["epoch", "wide_init"])
def test_fused_epoch_pull_compiles(one_chip, arms, pulls):
    """The epoch launch, and the wide init's (Q, capacity) launch whose
    index operands exceed SMEM unless chunked."""
    s = functools.partial(_spec, one_chip)
    c = _compile(lambda x, qs, a, b: ops.fused_epoch_pull(
        x, qs, a, b, block=BLOCK, impl="kernel"),
        s((CAP, D_PAD)), s((Q, D_PAD)), s((Q, arms), jnp.int32),
        s((Q, arms, pulls), jnp.int32))
    assert "tpu_custom_call" in c.as_text()


def test_block_pull_multi_compiles(one_chip):
    s = functools.partial(_spec, one_chip)
    c = _compile(lambda x, qs, a, b: ops.block_pull_multi(
        x, qs, a, b, block=BLOCK, impl="kernel"),
        s((CAP, D_PAD)), s((Q, D_PAD)), s((Q, B), jnp.int32),
        s((Q, B, T), jnp.int32))
    assert "tpu_custom_call" in c.as_text()


def test_block_pull_compiles(one_chip):
    s = functools.partial(_spec, one_chip)
    c = _compile(lambda x, q, a, b: ops.block_pull(
        x, q, a, b, block=BLOCK, impl="kernel"),
        s((CAP, D_PAD)), s((D_PAD,)), s((B,), jnp.int32),
        s((B, T), jnp.int32))
    assert "tpu_custom_call" in c.as_text()


@pytest.mark.parametrize("metric", ["l2", "l1"])
def test_pairwise_dist_compiles(one_chip, metric):
    s = functools.partial(_spec, one_chip)
    c = _compile(lambda q, x: ops.pairwise_dist(q, x, metric=metric,
                                                impl="kernel"),
                 s((Q, D_PAD)), s((CAP, D_PAD)))
    assert "tpu_custom_call" in c.as_text()


@pytest.mark.parametrize("rows", [Q, 4096], ids=["queries", "build_chunk"])
def test_rotation_compiles(one_chip, rows):
    c = _compile(ops.fwht, _spec(one_chip, (rows, D_PAD)))
    assert c.memory_analysis().temp_size_in_bytes <= 3 * rows * D_PAD * 4


def test_build_chunk_fits_one_chip(one_chip):
    """One build step writes a rotated row chunk into the donated store:
    the store is aliased, and the transient stays a small multiple of the
    chunk — so the paper shape builds on a 16 GiB chip."""
    from repro.index.builder import BUILD_ROWS, _write_rows
    s = functools.partial(_spec, one_chip)
    c = _write_rows.lower(s((CAP, D_PAD)), s((CAP,)),
                          s((BUILD_ROWS, 12288)), s((D_PAD,)),
                          s((), jnp.int32), block=BLOCK,
                          metric="l2").compile()
    m = c.memory_analysis()
    assert m.alias_size_in_bytes >= CAP * D_PAD * 4
    assert m.temp_size_in_bytes < 8 * BUILD_ROWS * D_PAD * 4


def test_wide_init_step_fits_one_chip(one_chip):
    """The race's wide init over every slot of every query: no
    lane-padded (Q, capacity, T0) temporaries."""
    from repro.configs.bmo_nn import DENSE
    from repro.index.batched_race import _fused_init
    s = functools.partial(_spec, one_chip)
    c = _fused_init.lower(s((CAP, D_PAD)), s((Q, D_PAD)),
                          s((CAP,), jnp.bool_), s((CAP,)),
                          s((2,), jnp.uint32), cfg=DENSE.bmo, block=BLOCK,
                          impl="kernel", prior_weight=4.0).compile()
    assert "tpu_custom_call" in c.as_text()
    assert c.memory_analysis().temp_size_in_bytes < 2**28


def test_init_and_epoch_pulls_keep_the_kernel_name(one_chip):
    """The wide init's launch and the epoch's are told apart by their
    scopes, while both custom calls keep the Pallas kernel's instruction
    name, ``fused_epoch_pull``, that the benchmark's roofline sums."""
    from repro.configs.bmo_nn import DENSE
    from repro.index.batched_race import _fused_epoch_step, _fused_init
    s = functools.partial(_spec, one_chip)
    cap, d, q = 1024, 1024, 8
    init_args = (s((cap, d)), s((q, d)), s((cap,), jnp.bool_), s((cap,)),
                 s((2,), jnp.uint32))
    init_kw = dict(cfg=DENSE.bmo, block=BLOCK, impl="kernel",
                   prior_weight=4.0)
    st, pool = jax.eval_shape(functools.partial(_fused_init, **init_kw),
                              *init_args)
    st = jax.tree_util.tree_map(lambda a: s(a.shape, a.dtype), st)
    texts = {
        "repro.fused_init_pull":
            _fused_init.lower(*init_args, **init_kw).compile().as_text(),
        "repro.fused_epoch_pull": _fused_epoch_step.lower(
            s((cap, d)), s((q, d)), st, s(pool.shape), cfg=DENSE.bmo,
            block=BLOCK, d=d, impl="kernel", eliminate=True,
            prior_weight=4.0, log_term=10.0, T=8).compile().as_text()}
    for scope, text in texts.items():
        assert _kernel_names(text) == {"fused_epoch_pull"}
        assert f"/{scope}/" in text
    assert "repro.fused_epoch_pull" not in texts["repro.fused_init_pull"]


def test_session_epoch_program_fits_one_chip(one_chip):
    """A session's epoch at the paper's shape and the widest frontier: one
    program runs the step's pull, the exactify and the summary. Its custom
    call keeps the kernel's instruction name under the epoch's scope, and
    its temporaries stay small beside the store."""
    from repro.configs.bmo_nn import DENSE
    from repro.index.anytime import _fused_epoch_snapshot
    from repro.index.batched_race import _fused_epoch_step, _fused_init
    s = functools.partial(_spec, one_chip)
    x, qs = s((CAP, D_PAD)), s((Q, D_PAD))
    st, pool = jax.eval_shape(functools.partial(
        _fused_init, cfg=DENSE.bmo, block=BLOCK, impl="kernel",
        prior_weight=4.0), x, qs, s((CAP,), jnp.bool_), s((CAP,)),
        s((2,), jnp.uint32))
    st = jax.tree_util.tree_map(lambda a: s(a.shape, a.dtype), st)
    c = _fused_epoch_snapshot.lower(
        x, qs, st, s(pool.shape), step=_fused_epoch_step, cfg=DENSE.bmo,
        block=BLOCK, d=12288, impl="kernel", eliminate=True,
        prior_weight=4.0, log_term=10.0, T=T).compile()
    text = c.as_text()
    assert _kernel_names(text) == {"fused_epoch_pull"}
    assert "/repro.fused_epoch_pull/" in text and "/repro.exactify/" in text
    assert c.memory_analysis().temp_size_in_bytes < 2**28


def _kernel_names(text):
    """Instruction names of the Pallas custom calls in compiled HLO text,
    without XLA's numeric suffixes."""
    import re
    calls = [ln for ln in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln]
    return {re.sub(r"(\.\d+)+$", "", ln.split(" = ", 1)[0].strip()
                   .lstrip("%")) for ln in calls}
