"""Compile the Pallas kernels for a TPU v5e without the chip.

The TPU compiler is installed with JAX and compiles for a described,
unattached ``v5e:2x2`` topology. What it refuses here (ops Mosaic cannot
lower, blocks off the (8, 128) tiling, VMEM overflow) would fail the first
call on the chip; interpret-mode parity tests cannot see any of it. Shapes
are the PDASC serving / build widths: d = 100 (GLOVE), 2^20 database rows,
beam candidates w = 1024, group length g = 1024 with 512 medoids.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and every test worker imports every
test file.
"""

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import distances, msa, nsa
from repro.kernels import kmedoids, ops, pairwise, quantized, topk
from repro.kernels.ref import packed_width

D = 100


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler in this install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip is written to the persistent cache but
    # cannot be read back without one; keep these compiles out of it.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, *shapes):
    compiled = jax.jit(fn).lower(*shapes).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("form,dtype", [
    ("l2", jnp.float32), ("cosine", jnp.float32), ("l1", jnp.float32),
    ("l2", jnp.bfloat16),
])
def test_pairwise_compiles(one_chip, form, dtype):
    x = jax.ShapeDtypeStruct((1024, D), dtype, sharding=one_chip)
    _compile(lambda a, b: pairwise.pairwise_pallas(a, b, form=form), x, x)


def test_knn_compiles(one_chip):
    q = jax.ShapeDtypeStruct((256, D), jnp.float32, sharding=one_chip)
    db = jax.ShapeDtypeStruct((1 << 20, D), jnp.float32, sharding=one_chip)
    _compile(lambda a, b: topk.knn_pallas(a, b, form="l2", k=10), q, db)


@pytest.mark.parametrize("form", ["l2", "dot", "l1"])
def test_rank_compiles(one_chip, form):
    b, w = 32, 1024
    S = lambda shape, dt=jnp.float32: jax.ShapeDtypeStruct(
        shape, dt, sharding=one_chip)
    _compile(
        lambda q, c, ok, cc: topk.rank_pallas(q, c, ok, cc, form=form, k=32),
        S((b, D)), S((b, w, D)), S((b, w), jnp.bool_), S((b, w)),
    )


@pytest.mark.parametrize("fmt,dtype", [
    ("dense", jnp.int8), ("dense", jnp.float16), ("int4", jnp.int8),
    ("binary", jnp.uint8),
])
def test_scan_compiles(one_chip, fmt, dtype):
    b, w = 32, 1024
    S = lambda shape, dt=jnp.float32: jax.ShapeDtypeStruct(
        shape, dt, sharding=one_chip)
    _compile(
        lambda q, c, s, ok: quantized.scan_pallas(q, c, s, ok, form="l2",
                                                  k=128, fmt=fmt),
        S((b, D)), S((b, w, packed_width(D, fmt)), dtype), S((b, w)),
        S((b, w), jnp.bool_),
    )


def test_swap_deltas_compiles(one_chip):
    g, k = 1024, 512
    S = lambda shape, dt=jnp.float32: jax.ShapeDtypeStruct(
        shape, dt, sharding=one_chip)
    _compile(
        lambda D_, d1, d2, n1, v: kmedoids.swap_deltas_pallas(
            D_, d1, d2, n1, v, k=k),
        S((g, g)), S((g,)), S((g,)), S((g,), jnp.int32), S((g,), jnp.bool_),
    )


def test_kernel_instruction_names_are_stable(one_chip):
    """A device trace names each op by its HLO instruction, and the
    benchmark finds the kernels by those names. Compile an outer program
    that gathers candidate rows and ranks them at a descent level and at
    the leaf (as ``nsa.search_beam`` does) and scans the gathered int8
    codes (as the two-stage scan does): every kernel instruction must be
    ``rank_pallas.N`` or ``scan_pallas.N``, whatever wraps the call."""
    b, n = 32, 1 << 16
    S = lambda shape, dt=jnp.float32: jax.ShapeDtypeStruct(
        shape, dt, sharding=one_chip)

    def serve(q, points, norms, idx_level, ok_level, idx_leaf, ok_leaf,
              codes, scales):
        out = []
        for idx, ok, k in ((idx_level, ok_level, 32), (idx_leaf, ok_leaf, 10)):
            out += topk.rank_pallas(q, jnp.take(points, idx, axis=0), ok,
                                    jnp.take(norms, idx), form="l2", k=k)
        out += quantized.scan_pallas(
            q, jnp.take(codes, idx_leaf, axis=0),
            jnp.take(scales, idx_leaf // 128), ok_leaf, form="l2", k=128)
        return out

    text = jax.jit(serve).lower(
        S((b, D)), S((n, D)), S((n,)), S((b, 1024), jnp.int32),
        S((b, 1024), jnp.bool_), S((b, 512), jnp.int32),
        S((b, 512), jnp.bool_), S((n, D), jnp.int8), S((n // 128,)),
    ).compile().as_text()
    names = [re.match(r"\s*(?:ROOT\s+)?%?([\w.-]+)\s*=", line).group(1)
             for line in text.splitlines() if "tpu_custom_call" in line]
    assert sorted(re.sub(r"\.\d+$", "", x) for x in names) == [
        "rank_pallas", "rank_pallas", "scan_pallas"]
    for x in names:
        assert re.match(r"^rank_pallas(\.\d+)?$", x) or \
            re.match(r"^scan_pallas(\.\d+)?$", x), x


@pytest.mark.parametrize("d", [D, 256])
def test_search_beam_ranks_runs_where_the_tables_lie(one_chip, monkeypatch, d):
    """Compile ``nsa.search_beam`` at batch 32, beam 32 over levels shaped
    like the GLOVE index's (each about half the one below, child runs of
    16-24). No level table may be copied into another layout: at d = 100
    the large tables are stored column-major and ``rank_pallas`` fetches
    their runs' lane blocks where they lie (a row gather would relayout each
    whole table on every call); at d = 256 they are row-major and gathered
    by rows. The 300-row level is row-major at both widths. Each ranked
    level stays one ``rank_pallas`` call, which the benchmark's roofline
    reads."""
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    monkeypatch.setattr(ops, "_layout_device",
                        lambda: next(iter(one_chip.device_set)))
    sizes, mcs = (1 << 16, 1 << 15, 1 << 14, 300, 150), (0, 24, 16, 19, 20)
    S = lambda shape, dt=jnp.float32: jax.ShapeDtypeStruct(
        shape, dt, sharding=one_chip)
    index = msa.PDASCIndexData(
        levels=tuple(msa.PDASCLevel(
            points=S((n, d)), valid=S((n,), jnp.bool_),
            parent=S((n,), jnp.int32), child_start=S((n,), jnp.int32),
            child_count=S((n,), jnp.int32), sq_norm=S((n,)))
            for n in sizes),
        leaf_ids=S((sizes[0],), jnp.int32))
    dist = distances.get("euclidean")
    text = jax.jit(lambda index, q: nsa.search_beam(
        index, q, dist=dist, k=10, r=1e9, beam=32, max_children=mcs),
    ).lower(index, S((32, d))).compile().as_text()

    # Every instruction that XLA names after a level table (the parameter,
    # and any copy of it) must keep the stored layout.
    lines = text.splitlines()
    table = re.compile(r"= \(?f32\[\d+,\d+\]\{([\d,]+)[:}].*"
                       r"op_name=\"index\.levels\[(\d+)\]\.points\"")
    stored = {}
    for line in lines:
        m = table.search(line)
        if m and "parameter(" in line:
            stored[m.group(2)] = m.group(1)
    assert len(stored) == len(sizes)
    for line in lines:
        m = table.search(line)
        if m:
            assert m.group(1) == stored[m.group(2)], f"relayout: {line}"

    kernels = [re.sub(r"\.\d+$", "", re.match(
        r"\s*(?:ROOT\s+)?%?([\w.-]+)\s*=", line).group(1))
        for line in lines if "tpu_custom_call" in line]
    ranked = len(sizes) - 1  # levels 3..1 and the leaf; the top is pairwise
    assert sorted(kernels) == ["pairwise_pallas"] + ["rank_pallas"] * ranked
