"""``trace_reduce.py`` on a hand-made profile whose numbers can be checked by
eye, and on a small trace recorded on the chip. Runs on a CPU:
``pytest benchmark/tests``."""

import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(BENCH))

import trace_reduce  # noqa: E402

RECORDED = Path(__file__).parent / "data" / "recorded.xplane.pb"


def xspace(planes):
    """Text proto of an XSpace: planes -> lines -> (name, start_ns, dur_ns)."""
    out = []
    for p_id, (plane, lines) in enumerate(planes.items()):
        names = sorted({e[0] for events in lines.values() for e in events})
        meta = {n: i + 1 for i, n in enumerate(names)}
        out.append(f'planes {{ id: {p_id} name: "{plane}"')
        for l_id, (line, events) in enumerate(lines.items()):
            out.append(f'  lines {{ id: {l_id} name: "{line}" timestamp_ns: 0')
            for name, start, dur in events:
                out.append(f"    events {{ metadata_id: {meta[name]} offset_ps: {start * 1000} "
                           f"duration_ps: {dur * 1000} }}")
            out.append("  }")
        for name, i in meta.items():
            out.append(f'  event_metadata {{ key: {i} value {{ id: {i} name: "{name}" }} }}')
        out.append("}")
    return "\n".join(out)


def profile(planes):
    from jax.profiler import ProfileData

    return ProfileData.from_text_proto(xspace(planes))


HAND_MADE = {
    # chip 0: busy 0-100, 100-150 (touching), 400-500, 700-1000 -> 550 of 1000
    "/device:TPU:0": {
        "XLA Ops": [("fusion.1", 0, 100), ("fusion.2", 100, 50), ("all-reduce.3", 400, 100),
                    ("fusion.1", 700, 300), ("copy.4", 750, 100)],  # copy nested in fusion
        "Steps": [("step 1", 0, 1000)],  # not an operation line
    },
    # chip 1: busy 0-250 with a fusion; an overlapped all-gather 0-250 on the async line
    "/device:TPU:1": {"XLA Ops": [("fusion.8", 0, 250)],
                      "Async XLA Ops": [("all-gather-start.9", 0, 250)]},
    "/device:TPU:0 SparseCore 0": {"XLA Ops": [("sc", 0, 1000)]},  # left out
    "/host:CPU": {
        "main": [("bench:input_wait", 150, 240), ("bench:update_call", 500, 90),
                 ("not ours", 0, 1000)],
    },
}


def test_merge_and_overlap():
    assert trace_reduce.merge([(5, 7), (0, 2), (1, 3), (3, 4)]) == [(0, 4), (5, 7)]
    assert trace_reduce.total([(0, 4), (5, 7)]) == 6
    assert trace_reduce.overlap([(0, 4), (5, 7)], 3, 6) == 2


def test_names():
    assert trace_reduce.is_collective("all-reduce.12")
    assert trace_reduce.is_collective("%reduce-scatter-start.1")
    assert not trace_reduce.is_collective("fusion.3") and not trace_reduce.is_collective("reduce.7")
    text = "%fusion.5 = f32[16384,96]{1,0:T(8,128)S(1)} fusion(f32[16384,96]{1,0} %bitcast.536)"
    assert trace_reduce.op_name(text) == "fusion.5 f32[16384,96]"
    assert trace_reduce.op_name("fusion.7") == "fusion.7"
    assert trace_reduce.is_collective(text) is False
    assert trace_reduce.is_collective("%all-reduce.3 = f32[8]{0} all-reduce(f32[8]{0} %x)")


def test_hand_made_profile():
    s = trace_reduce.reduce_data(profile(HAND_MADE))
    assert s["chips"] == 2  # the SparseCore plane is not a chip
    assert s["window_s"] == pytest.approx(1000e-9)
    # busy union: chip 0 550 ns, chip 1 250 ns -> mean 400 ns, idle 60%
    assert s["busy_s_by_chip"]["/device:TPU:0"] == pytest.approx(550e-9)
    assert s["busy_s"] == pytest.approx(400e-9)
    assert s["idle_share"] == pytest.approx(0.6)
    # collectives: chip 0 100 ns, chip 1 250 ns -> mean 175 ns of 1000
    assert s["collective_share"] == pytest.approx(0.175)
    # operations on the first chip, by family, longest first
    assert s["device_ops"][0] == ["fusion.1", pytest.approx(400e-9)]
    assert dict(map(tuple, s["device_ops"]))["all-reduce.3"] == pytest.approx(100e-9)
    # gaps on chip 0: 150-400 (input_wait covers 240 of 250), 500-700
    # (update_call covers 90 of 200: under half, so "host: other")
    gaps = dict(map(tuple, s["idle_gaps"]))
    assert gaps["bench:input_wait"] == pytest.approx(250e-9)
    assert gaps["host: other"] == pytest.approx(200e-9)
    assert s["n_gaps"] == 2 and s["longest_gap_s"] == pytest.approx(250e-9)


def test_slice_and_cuts():
    """The same profile, but the harness marked 100-900 as its slice and cut
    400-500 (an update call that compiled) out of it."""
    planes = {k: dict(v) for k, v in HAND_MADE.items()}
    planes["/host:CPU"] = {"main": HAND_MADE["/host:CPU"]["main"] + [("bench:slice", 100, 800)]}
    s = trace_reduce.reduce_data(profile(planes), cuts_s=[(0.3e-6, 0.4e-6)])
    assert s["cut_s"] == pytest.approx(100e-9)
    assert s["window_s"] == pytest.approx(700e-9)  # 800 less the cut
    # chip 0 inside the slice, outside the cut: 100-150 and 700-900 -> 250;
    # chip 1: 100-250 -> 150; mean 200 of 700
    assert s["busy_s"] == pytest.approx(200e-9)
    assert s["idle_share"] == pytest.approx(1 - 200 / 700)
    assert s["collective_share"] == pytest.approx((0 + 150) / 2 / 700)  # the all-reduce was cut
    # gaps on chip 0: 150-400 (input_wait covers 240) and 500-700 (other)
    gaps = dict(map(tuple, s["idle_gaps"]))
    assert gaps["bench:input_wait"] == pytest.approx(250e-9)
    assert gaps["host: other"] == pytest.approx(200e-9)
    assert "bench:slice" not in gaps and s["n_gaps"] == 2


def with_kernels():
    """The hand-made profile with a step program, eleven fusions longer than
    either kernel, and two of the program's kernels: ``srt_flash_fwd`` twice
    (``.16`` and ``.17``, 3 + 2 ns) and ``srt_tiny`` once (1 ns), so that
    neither is among the ten longest rows."""
    planes = {k: dict(v) for k, v in HAND_MADE.items()}
    ops = list(HAND_MADE["/device:TPU:0"]["XLA Ops"])
    ops += [(f"fusion.{100 + i}", 160 + 20 * i, 10) for i in range(11)]  # 160 .. 370
    ops += [("%srt_flash_fwd.16 = (bf16[64,12,256,128]{3,2,1,0}, f32[64,12,1,256]{3,2,1,0}) "
             "custom-call(bf16[64,12,256,128]{3,2,1,0} %x)", 600, 3),
            ("srt_flash_fwd.17", 610, 2), ("%srt_tiny = f32[8]{0} custom-call(f32[8]{0} %y)", 620, 1),
            ("not_srt_fused.2", 630, 1)]
    planes["/device:TPU:0"]["XLA Ops"] = ops
    planes["/device:TPU:0"]["XLA Modules"] = [
        ("jit_srt_train_step(4589997382407016891)", 0, 150),
        ("jit__threefry_split(77)", 390, 5),
        ("jit_srt_train_step(4589997382407016891)", 400, 90),
        ("jit_srt_train_step(4589997382407016891)", 700, 300)]
    # the second chip's kernels are not added in: the first chip's plane, like device_ops
    planes["/device:TPU:1"]["XLA Ops"] = HAND_MADE["/device:TPU:1"]["XLA Ops"] + [
        ("srt_flash_fwd.16", 600, 3)]
    return planes


def test_kernels_and_steps():
    s = trace_reduce.reduce_data(profile(with_kernels()))
    top = [name for name, _ in s["device_ops"]]
    assert len(top) == 10 and not any(name.startswith("srt_") for name in top)
    assert s["kernels_s"] == {"srt_flash_fwd": pytest.approx(5e-9), "srt_tiny": pytest.approx(1e-9)}
    assert s["steps"] == 3
    # the numbers the accepted readers take are those of the profile without them,
    # but for the 110 + 7 ns the new operations keep chip 0 busy
    base = trace_reduce.reduce_data(profile(HAND_MADE))
    assert s["busy_s_by_chip"]["/device:TPU:0"] == pytest.approx(
        base["busy_s_by_chip"]["/device:TPU:0"] + 117e-9)
    assert s["collective_share"] == base["collective_share"]
    assert base["kernels_s"] == {} and base["steps"] == 0


def test_kernels_and_steps_inside_the_slice():
    """Slice 100-900 with 400-500 cut: the step whose middle lies before the
    slice and the one inside the cut are not the slice's; the step that began
    3 ns before the slice (the clocks' skew) is; the kernels at 600-621 are."""
    planes = with_kernels()
    planes["/host:CPU"] = {"main": HAND_MADE["/host:CPU"]["main"] + [("bench:slice", 100, 800)]}
    planes["/device:TPU:0"]["XLA Modules"] = planes["/device:TPU:0"]["XLA Modules"] + [
        ("jit_srt_train_step(4589997382407016891)", 97, 50)]
    s = trace_reduce.reduce_data(profile(planes), cuts_s=[(0.3e-6, 0.4e-6)])
    assert s["steps"] == 2  # 97-147 and 700-1000 (middle 850); not 0-150, not 400-490
    assert s["kernels_s"] == {"srt_flash_fwd": pytest.approx(5e-9), "srt_tiny": pytest.approx(1e-9)}
    planes["/host:CPU"] = {"main": [("bench:slice", 100, 503)]}  # ends with the first kernel
    s = trace_reduce.reduce_data(profile(planes))
    assert s["kernels_s"] == {"srt_flash_fwd": pytest.approx(3e-9)} and s["steps"] == 2


def test_kernel_names():
    assert trace_reduce.kernel_name("%srt_fused_adam.56 = (f32[8192,128]) custom-call()") == "srt_fused_adam"
    assert trace_reduce.kernel_name("srt_hash_embed") == "srt_hash_embed"
    assert trace_reduce.kernel_name("fusion.5") is None
    assert trace_reduce.kernel_name("%while.202 = (s32[]) while(...)") is None


def test_subtract():
    assert trace_reduce.subtract([(0, 10), (20, 30)], [(5, 22), (25, 26)]) == [
        (0, 5), (22, 25), (26, 30)]
    assert trace_reduce.clip([(0, 10), (20, 30)], 5, 25) == [(5, 10), (20, 25)]


def test_no_device_plane_gives_nothing():
    host_only = {"/host:CPU": {"main": [("bench:input_wait", 0, 10)]}}
    assert trace_reduce.reduce_data(profile(host_only)) is None


@pytest.mark.skipif(not RECORDED.is_file(), reason="no recorded trace beside the test")
def test_recorded_chip_trace():
    s = trace_reduce.reduce_file(RECORDED)
    assert s is not None and s["chips"] >= 1
    assert 0.0 < s["busy_s"] < s["window_s"]
    assert 0.0 < s["idle_share"] < 1.0
    assert s["device_ops"] and all(sec > 0 for _, sec in s["device_ops"])
    assert s["idle_gaps"]
