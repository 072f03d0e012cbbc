"""Operations, bytes and roofline shares of the kernels' call shapes."""

import _paths  # noqa: F401
import pytest

from annbench import pipeline, roofline, spec

PLAN = dict(batch=32, d=100, k=10, beam=32, execution="beam",
            rerank_width=128, level_sizes=[1 << 20, 1 << 19, 1 << 18, 1024],
            max_children=[0, 9, 7, 5])


def test_descent_widths():
    ranks, leaf_w = pipeline.descent(PLAN)
    # top: 32 of 1024 prototypes, each with <= 5 children -> 160 at level 2
    assert ranks == [(160, 32), (224, 32)]
    assert leaf_w == 32 * 9


def test_rank_calls_per_execution():
    rank = spec.kernel("rank_pallas")
    beam = rank.calls(PLAN)
    assert [c["w"] for c in beam] == [160, 224, 288]
    assert beam[-1]["k"] == 10
    two = rank.calls(dict(PLAN, execution="two_stage"))
    assert [c["w"] for c in two] == [160, 224, 128]
    assert spec.kernel("scan_pallas").calls(PLAN) == []
    (scan,) = spec.kernel("scan_pallas").calls(dict(PLAN,
                                                    execution="two_stage"))
    assert (scan["w"], scan["k"]) == (288, 128)


def test_rank_cost_is_memory_bound_on_v5e():
    flops, nbytes = spec.kernel("rank_pallas").cost(
        dict(b=32, w=288, d=100, k=10))
    assert flops == 2 * 32 * 288 * 100 + 3 * 32 * 288
    assert nbytes == 4 * 32 * 288 * 100 + 5 * 32 * 288 + 4 * 32 * 100 \
        + 8 * 32 * 10
    # arithmetic intensity ~0.5 flop/byte, far under the v5e ridge (~240)
    assert flops / nbytes < 1


def test_scan_cost_counts_one_byte_per_code():
    flops, nbytes = spec.kernel("scan_pallas").cost(
        dict(b=32, w=288, d=100, k=128))
    assert nbytes == 32 * 288 * 100 + 5 * 32 * 288 + 4 * 32 * 100 \
        + 8 * 32 * 128
    assert flops == 3 * 32 * 288 * 100 + 3 * 32 * 288


def _ctx(events, kind="TPU v5 lite"):
    return dict(plan=PLAN, device_kind=kind,
                trace=dict(device=events, host=[]))


def test_roofline_share_from_events():
    rank = spec.kernel("rank_pallas")
    least = sum(rank.cost(c)[1] / 819e9 for c in rank.calls(PLAN))
    # two batches of three rank calls, each batch taking 4x its least time
    per = 4 * least / 3 * 1e9
    events = [("rank_pallas.1", i * 1e6, per) for i in range(6)]
    events.append(("fusion.3", 0.0, 1e6))
    assert roofline.share(_ctx(events), "rank_pallas") == pytest.approx(25.0)


def test_roofline_is_silent_without_its_kernel():
    assert roofline.share(_ctx([("fusion.1", 0.0, 1e3)]), "rank_pallas") \
        is None
    assert roofline.share(_ctx([("scan_pallas", 0.0, 1e3)]),
                          "scan_pallas") is None  # no scan in a beam plan
    assert roofline.share(dict(_ctx([]), trace=None), "rank_pallas") is None


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        roofline.share(_ctx([("rank_pallas.3", 0.0, 1e3)], kind="TPU v9"),
                       "rank_pallas")
