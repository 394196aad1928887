"""The trace reduction, on small traces recorded on a TPU v5 lite: one
``sv:composed`` job and one ``reach:basic`` serving session at scale 10,
each inside a host span named ``bench/<tag>``."""
import gzip

import jax
import pytest

import kernel_cost
import trace
from conftest import HERE

DATA = HERE / "data"


def load(tag: str) -> trace.Trace:
    raw = gzip.decompress((DATA / f"{tag}.xplane.pb.gz").read_bytes())
    return trace.reduce(jax.profiler.ProfileData.from_serialized_xspace(raw),
                        span=f"bench/{tag}")


@pytest.fixture(scope="module", params=["cc_s10_sv_composed", "serve_s10"])
def t(request):
    return load(request.param)


def test_window_and_busy_time(t):
    assert t.window_s > 0
    assert 0 < t.busy_s() <= t.window_s
    assert 0 <= t.idle_share() < 1


def test_self_times_add_up_to_busy_time(t):
    # nested operations are charged once: self times sum to the union
    total = sum(s for ops in t.devices.values()
                for _, s in trace._self_times(ops)) * 1e-9
    assert total == pytest.approx(t.busy_s(), rel=1e-6)


def test_breakdown(t):
    ops = t.top_ops(10)
    assert 0 < len(ops) <= 10
    assert all(s > 0 for _, s in ops)
    assert [s for _, s in ops] == sorted((s for _, s in ops), reverse=True)
    assert not any("{" in name for name, _ in ops)   # short names
    gaps = t.idle_gaps(10)
    assert 0 < len(gaps) <= 10
    assert sum(s for _, s in gaps) <= t.window_s - t.busy_s() + 1e-9


def test_short_name():
    hlo = ("%fusion.286 = s32[33554432]{0:T(1024)} fusion(s32[8,131072]"
           "{1,0:T(8,128)S(1)} %custom-call.66), kind=kCustom")
    assert trace.short_name(hlo) == "fusion.286 fusion s32[33554432]"
    tup = ("%body.7 = (s32[8,1,8,128]{3,2,1,0:T(8,128)S(1)}, s32[8,1,9]"
           "{2,1,0:T(1,128)S(1)}) custom-call(s32[8,1,8,128] %reshape.900)")
    assert trace.short_name(tup) == "body.7 custom-call s32[8,1,8,128]"


def test_kernel_calls_found():
    sv = load("cc_s10_sv_composed")
    seg = list(kernel_cost.calls(sv, "segment_combine"))
    route = list(kernel_cost.calls(sv, "bucket_route"))
    assert len(seg) == 24          # 3 supersteps x 8 workers
    assert route and all(c[1] > 0 for c in route)
    serve = load("serve_s10")
    assert not list(kernel_cost.calls(serve, "segment_combine"))
    lanes = list(kernel_cost.calls(serve, "bucket_route"))
    assert len(lanes) == 13        # one lane-aware call per superstep
    # keys (4 B) + ranks (4 B) + 8 lane masks (4 B) per routed slot
    assert lanes[0][1] == 8 * 1024 * (4 + 4 + 8 * 4)


def test_segment_combine_cost():
    hlo = ("%closed_call.10 = s32[8,1,128]{2,1,0:T(1,128)S(1)} custom-call("
           "s32[8]{0:T(128)S(1)} %fusion.375, s32[8]{0:T(128)S(1)} %f.376, "
           "s32[8,4,128]{2,1,0:T(4,128)S(1)} %d.4, s32[1,8,4,128]{3,2,1,0:"
           "T(4,128)S(1)} %d.5), custom_call_target=\"tpu_custom_call\", "
           "operand_layout_constraints={s32[8]{0}, s32[8]{0}}")
    sig = kernel_cost.signature(hlo)
    assert kernel_cost.segment_combine(*sig) == (4096 * 4 * 2 + 1024 * 4,
                                                 4096)
    assert kernel_cost.bucket_route(*sig) is None


def test_roofline_share_is_a_share(t):
    for kernel in kernel_cost.KERNELS:
        share = kernel_cost.roofline_share(t, kernel, "TPU v5 lite")
        assert share is None or 0 < share <= 100


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        kernel_cost.peaks("TPU v99")
