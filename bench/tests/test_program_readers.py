"""The readers of the program's own spans (``program_spans``) on synthetic
profiles with known gaps, and on hand-built spans.

The profile: four decode steps on the chip, an admission (with its
prefill) between the second and the third, and the host's ticks around
them.  Times below are in milliseconds."""
import types

import jax
import pytest

import harness
import program_spans
import trace_reduce

MS = 1_000_000  # ns

HOST = [  # (line, name, start, end, attrs)
    ("python", "bench.window", 0.0, 100.0, {}),
    ("python", "bench.step", 7.9, 21.6, {}),
    ("python", "repro.sched.step", 8.0, 21.5, {"tick": 0, "rows": 2, "admitted": 0}),
    ("python", "bench.decode", 8.1, 21.02, {}),
    ("python", "repro.runner.launch", 8.2, 8.7, {}),
    ("python", "repro.runner.fetch", 8.8, 21.0, {}),
    ("python", "repro.runner.sample", 21.05, 21.35, {}),
    ("python", "repro.sched.step", 22.0, 35.8, {"tick": 1, "rows": 2, "admitted": 0}),
    ("python", "repro.runner.launch", 22.5, 23.5, {}),
    ("python", "repro.runner.fetch", 23.6, 35.5, {}),
    ("python", "repro.runner.sample", 35.55, 35.75, {}),
    ("python", "repro.sched.step", 35.9, 51.6, {"tick": 2, "rows": 3, "admitted": 1}),
    ("python", "bench.admit", 35.95, 38.5, {}),  # blocks on the prefill
    ("python", "repro.runner.admit", 36.0, 37.0,
     {"rid": 7, "slot": 2, "prompt": 40, "bucket": 64}),
    ("python", "repro.runner.launch", 38.6, 39.5, {}),
    ("python", "repro.runner.fetch", 39.6, 51.2, {}),
    ("python", "repro.runner.sample", 51.3, 51.5, {}),
    ("python", "repro.sched.step", 52.0, 64.7, {"tick": 3, "rows": 3, "admitted": 0}),
    ("python", "repro.runner.launch", 52.2, 52.8, {}),
    ("python", "repro.runner.fetch", 52.9, 64.0, {}),
    ("python", "repro.runner.sample", 64.1, 64.3, {}),
    ("other", "bench.wait", 52.5, 60.0, {}),  # another thread: not inside a tick
    ("python", "repro.runner.admit", 120.0, 121.0,  # after the traced window
     {"rid": 8, "slot": 0, "prompt": 10, "bucket": 32}),
]
MODULES = [("jit_decode_step", 10.0, 20.0), ("jit_decode_step", 24.0, 34.0),
           ("jit_prefill", 36.0, 38.0), ("jit_decode_step", 40.0, 50.0),
           ("jit_decode_step", 53.0, 63.0)]


def _text_proto(host, modules):
    """An XSpace in text form: one host plane, one TPU plane whose every
    program is one operation."""
    names, stats = {}, {}

    def nid(table, name):
        return table.setdefault(name, len(table) + 1)

    def event(name, a, b, attrs=None):
        st = "".join(f" stats {{ metadata_id: {nid(stats, k)} int64_value: {v} }}"
                     for k, v in (attrs or {}).items())
        return (f"events {{ metadata_id: {nid(names, name)} offset_ps: {round(a * MS * 1000)} "
                f"duration_ps: {round(b * MS * 1000) - round(a * MS * 1000)}{st} }}")

    lines = {}
    for line, name, a, b, attrs in host:
        lines.setdefault(line, []).append(event(name, a, b, attrs))
    host_lines = "".join(f'lines {{ id: {i} name: "{line}" timestamp_ns: 0 {" ".join(evs)} }}\n'
                         for i, (line, evs) in enumerate(lines.items(), 1))

    def meta(table, kind):
        return "".join(f'{kind} {{ key: {i} value {{ id: {i} name: "{n}" }} }}\n'
                       for n, i in table.items())

    host_plane = f'planes {{ id: 1 name: "/host:CPU" {host_lines}{meta(names, "event_metadata")}' \
                 f'{meta(stats, "stat_metadata")} }}\n'
    names.clear()
    mods = " ".join(event(f"{p}(1)", a, b) for p, a, b in modules)
    ops = " ".join(event(f"%fusion.{i} = f32[8] fusion()", a, b)
                   for i, (_, a, b) in enumerate(modules))
    device_plane = (f'planes {{ id: 2 name: "/device:TPU:0" '
                    f'lines {{ id: 1 name: "XLA Modules" timestamp_ns: 0 {mods} }} '
                    f'lines {{ id: 2 name: "XLA Ops" timestamp_ns: 0 {ops} }} '
                    f'{meta(names, "event_metadata")} }}\n')
    return host_plane + device_plane


def _ctx(host=HOST, modules=MODULES):
    profile = jax.profiler.ProfileData.from_text_proto(_text_proto(host, modules))
    return types.SimpleNamespace(reduced=trace_reduce.reduce(profile),
                                 program_spans=program_spans.spans(profile))


def _read(metric, ctx):
    return harness.load_reader(metric)(ctx)


def test_ticks_leave_out_the_admission():
    ticks = program_spans.ticks(_ctx())
    assert [(a.start / MS, b.start / MS) for a, b in ticks] == [(10.0, 24.0), (40.0, 53.0)]


def test_device_clock_readers():
    ctx = _ctx()
    # idle 20-24 and 50-53; 34-40 holds the admission
    assert _read("tick_idle_ms", ctx) == pytest.approx(3.5)
    # fetches end 1.0 after the first step and 1.2 after the third
    assert _read("logits_fetch_ms", ctx) == pytest.approx(1.1)
    # launches start 1.5 before the second step and 0.8 before the fourth;
    # the spans run on 0.5 and 0.2 past those starts, which is left out
    assert _read("launch_ms", ctx) == pytest.approx(1.15)


def test_host_readers_on_the_profile():
    ctx = _ctx()
    assert _read("sample_ms", ctx) == pytest.approx(0.2)
    # ticks less what the spans inside them cover: 13.5 - 13.22,
    # 13.8 - 13.1, 15.7 - 15.25 (the benchmark's block on the prefill
    # included), 12.7 - 11.9 (the other thread's wait left out)
    assert _read("sched_self_ms", ctx) == pytest.approx((0.45 + 0.7) / 2)
    # the admission after the window is left out
    assert _read("prefill_useful_share", ctx) == pytest.approx(62.5)


def test_the_profile_is_read_from_the_runs_trace_directory(tmp_path, monkeypatch):
    monkeypatch.setattr(harness, "ROOT", tmp_path)
    directory = tmp_path / ".bench_trace" / "ideal-decode" / "plugins" / "profile" / "run"
    directory.mkdir(parents=True)
    text = _text_proto(HOST, MODULES)
    (directory / "host.xplane.pb").write_bytes(
        jax.profiler.ProfileData.text_proto_to_serialized_xspace(text))
    ctx = types.SimpleNamespace(
        cell=types.SimpleNamespace(name="ideal-decode"),
        reduced=trace_reduce.reduce(jax.profiler.ProfileData.from_text_proto(text)))
    assert _read("sample_ms", ctx) == pytest.approx(0.2)
    assert _read("launch_ms", ctx) == pytest.approx(1.15)
    assert len(ctx.program_spans) == len(HOST)


METRICS = ["tick_idle_ms", "logits_fetch_ms", "launch_ms", "sample_ms", "sched_self_ms",
           "prefill_useful_share"]


@pytest.mark.parametrize("metric", METRICS)
def test_a_program_without_spans_reports_nothing(metric):
    bench_only = [h for h in HOST if not h[1].startswith("repro.")]
    ctx = _ctx(host=bench_only)
    assert ctx.program_spans == []
    assert _read(metric, ctx) is None


def _hand_built(*spans):
    window = types.SimpleNamespace(window=(0.0, 100.0 * MS), programs=[], gaps=[])
    return types.SimpleNamespace(reduced=window, program_spans=[
        program_spans.Span(name, a * MS, b * MS, attrs, "python") for name, a, b, attrs in spans])


def test_host_readers_on_hand_built_spans():
    ctx = _hand_built(
        ("repro.sched.step", 1.0, 5.0, {"tick": 0, "rows": 1, "admitted": 1}),
        ("repro.runner.admit", 1.5, 2.5, {"rid": 0, "slot": 0, "prompt": 300, "bucket": 512}),
        ("repro.runner.admit", 2.6, 3.0, {"rid": 1, "slot": 1, "prompt": 900, "bucket": 1024}),
        ("repro.runner.launch", 3.1, 3.3, {}),
        ("repro.runner.fetch", 3.3, 4.5, {}),
        ("repro.runner.sample", 4.5, 4.9, {}))
    assert _read("sample_ms", ctx) == pytest.approx(0.4)
    assert _read("sched_self_ms", ctx) == pytest.approx(4.0 - 1.0 - 0.4 - 0.2 - 1.2 - 0.4)
    assert _read("prefill_useful_share", ctx) == pytest.approx(100.0 * 1200 / 1536)
    # no steps on the chip: no tick to read
    for metric in ("tick_idle_ms", "logits_fetch_ms", "launch_ms"):
        assert _read(metric, ctx) is None


@pytest.mark.parametrize("metric,missing", [
    ("sample_ms", "repro.runner.sample"),
    ("sched_self_ms", "repro.sched.step"), ("prefill_useful_share", "repro.runner.admit")])
def test_host_readers_report_nothing_without_their_span(metric, missing):
    spans = [("repro.sched.step", 1.0, 5.0, {"tick": 0, "rows": 1, "admitted": 1}),
             ("repro.runner.admit", 1.5, 2.5, {"rid": 0, "slot": 0, "prompt": 3, "bucket": 32}),
             ("repro.runner.launch", 3.1, 3.3, {}),
             ("repro.runner.sample", 4.5, 4.9, {})]
    ctx = _hand_built(*[s for s in spans if s[0] != missing])
    assert _read(metric, ctx) is None


@pytest.mark.parametrize("metric,missing", [
    ("launch_ms", "repro.runner.launch"), ("logits_fetch_ms", "repro.runner.fetch"),
    ("tick_idle_ms", "repro.sched.step")])
def test_device_clock_readers_report_nothing_without_their_span(metric, missing):
    ctx = _ctx(host=[h for h in HOST if h[1] != missing])
    assert ctx.program_spans
    assert _read(metric, ctx) is None
