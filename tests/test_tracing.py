"""``serving.tracing``: the spans of the scheduler's ticks and the runner's
calls, read back from a CPU profile captured while the scheduler serves;
tokens do not depend on them."""
import dataclasses
import glob
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.noise_sweep import tiny_lm_config
from repro.models import model as M
from repro.serving import ContinuousBatchingScheduler, ModelRunner, tracing

pytestmark = pytest.mark.serving

MAX_SEQ = 64
# (prompt length, new tokens): three requests over two slots, so the third
# is admitted mid-flight; the prompts fall in buckets 32 and 64
REQUESTS = [(5, 3), (40, 4), (9, 2)]
RUNNER_SPANS = {"runner.admit", "runner.launch", "runner.fetch", "runner.sample"}


@dataclasses.dataclass
class Event:
    name: str  # without the prefix, e.g. "runner.fetch"
    start: float
    end: float
    stats: Dict
    line: str


@pytest.fixture(scope="module")
def tiny_lm():
    cfg = tiny_lm_config()
    params, _ = M.init_model(jax.random.PRNGKey(0), cfg, dtype=jnp.float32)
    return cfg, params


def _prompt(n, lo):
    return (np.arange(lo, lo + n) % 60 + 1).astype(np.int32)


def _serve(tiny_lm, temperature=0.0):
    cfg, params = tiny_lm
    runner = ModelRunner(cfg, params, max_seq=MAX_SEQ, temperature=temperature, seed=3)
    sched = ContinuousBatchingScheduler(runner, max_batch=2)
    for i, (n, k) in enumerate(REQUESTS):
        sched.submit(_prompt(n, i), max_new_tokens=k)
    return [list(r.generated) for r in sched.run()]


def _profiled(directory, fn) -> List[Event]:
    """The ``repro.*`` events on the host planes of a profile of ``fn()``,
    in start order."""
    jax.profiler.start_trace(str(directory))
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(directory / "**" / "*.xplane.pb"), recursive=True)
    events = [Event(ev.name[len(tracing.PREFIX):], ev.start_ns, ev.end_ns, dict(ev.stats),
                    f"{plane.name}/{line.name}")
              for plane in jax.profiler.ProfileData.from_file(path).planes
              if plane.name.startswith("/host:")
              for line in plane.lines for ev in line.events
              if ev.name.startswith(tracing.PREFIX)]
    return sorted(events, key=lambda e: e.start)


@pytest.fixture(scope="module")
def served(tiny_lm, tmp_path_factory):
    """Tokens and spans of one run of the requests under a profile, after
    one run that compiles outside it."""
    _serve(tiny_lm)
    out = {}
    events = _profiled(tmp_path_factory.mktemp("prof"),
                       lambda: out.setdefault("tokens", _serve(tiny_lm)))
    return out["tokens"], events


def _parent(ev: Event, events: List[Event]):
    """The innermost event on ``ev``'s thread that holds it."""
    holders = [p for p in events if p is not ev and p.line == ev.line
               and p.start <= ev.start and ev.end <= p.end]
    return min(holders, key=lambda p: p.end - p.start, default=None)


def test_runner_spans_nest_in_scheduler_ticks(served):
    _, events = served
    assert {e.name for e in events} == RUNNER_SPANS | {"sched.step"}
    steps = [e for e in events if e.name == "sched.step"]
    assert all(_parent(s, events) is None for s in steps)
    kids = {id(s): [] for s in steps}
    for e in events:
        if e.name in RUNNER_SPANS:
            parent = _parent(e, events)
            assert parent is not None and parent.name == "sched.step"
            kids[id(parent)].append(e.name)
    # one launch, fetch and sample in every tick that decodes a row, in order
    for s in steps:
        names = kids[id(s)]
        want = ["runner.launch", "runner.fetch", "runner.sample"] if s.stats["rows"] else []
        assert [k for k in names if k != "runner.admit"] == want
        assert names.count("runner.admit") == s.stats["admitted"]
    assert sum(s.stats["admitted"] for s in steps) == len(REQUESTS)
    assert max(s.stats["rows"] for s in steps) == 2


def test_ticks_are_numbered_from_zero(served):
    _, events = served
    ticks = [e.stats["tick"] for e in events if e.name == "sched.step"]
    assert ticks == list(range(len(ticks)))
    # the last tick retires the last request: every request decodes
    assert len(ticks) >= max(k for _, k in REQUESTS)


def test_admit_attrs_match_prompt_and_bucket(served):
    _, events = served
    admits = sorted((e.stats for e in events if e.name == "runner.admit"),
                    key=lambda a: a["rid"])
    assert [(a["rid"], a["prompt"], a["bucket"]) for a in admits] == [
        (0, 5, 32), (1, 40, 64), (2, 9, 32)]
    assert {a["slot"] for a in admits[:2]} == {0, 1}
    assert admits[2]["slot"] in (0, 1)


def test_profile_holds_spans_with_attrs_as_stats(served):
    _, events = served
    steps = [e.stats for e in events if e.name == "sched.step"]
    assert steps and all({"tick", "rows", "admitted"} <= set(s) for s in steps)
    for name in RUNNER_SPANS - {"runner.admit"}:
        assert all(e.stats == {} for e in events if e.name == name)


@pytest.mark.parametrize("temperature", [0.0, 0.8])
def test_tokens_bit_identical_with_profile_on_and_off(tiny_lm, tmp_path, temperature):
    off = _serve(tiny_lm, temperature)
    out = {}
    events = _profiled(tmp_path, lambda: out.setdefault("on", _serve(tiny_lm, temperature)))
    assert events
    assert out["on"] == off
    assert off == _serve(tiny_lm, temperature)


def test_set_metadata_adds_attrs_known_inside_the_span(tmp_path):
    def body():
        with tracing.span("sched.step", tick=4) as sp:
            sp.set_metadata(rows=2, admitted=1)

    (ev,) = _profiled(tmp_path, body)
    assert (ev.name, ev.stats) == ("sched.step", {"tick": 4, "rows": 2, "admitted": 1})


def test_span_closes_when_its_body_raises(tmp_path):
    def body():
        with pytest.raises(ValueError):
            with tracing.span("runner.sample"):
                raise ValueError("sampling failed")
        with tracing.span("runner.fetch"):
            pass

    sample, fetch = _profiled(tmp_path, body)
    assert (sample.name, fetch.name) == ("runner.sample", "runner.fetch")
    # the failed span ended where it raised: the next one is not inside it
    assert sample.end <= fetch.start
