"""The stage reduction of a trace (bench/scopes.py): op -> scope maps,
device seconds per scope, idle gaps named by the innermost host span,
and the host's own time per round."""

import os

import jax
import jax.numpy as jnp
import pytest

import scopes
import trace_reduce as tr
from trace_reduce import Interval

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def test_op_scopes_of_a_small_jitted_function():
    def decode_round(x, y):
        with jax.named_scope("ess.indexer"):
            z = x @ y
        with jax.named_scope("ess.attend"):
            with jax.named_scope("ess.pool"):
                w = jnp.sin(z) + 1.0
            v = w @ y
        return jnp.sort(v, axis=-1)

    text = jax.jit(decode_round).lower(
        jnp.ones((8, 8)), jnp.ones((8, 8))).compile().as_text()
    module, table = scopes.op_scopes(text)
    assert module == "jit_decode_round"
    got = {}
    for line in text.splitlines():
        for op in (" dot(", " sort(", " sine("):
            if op in line:
                name = line.split("=", 1)[0].strip().split()[-1].lstrip("%")
                got.setdefault(op.strip(" ("), set()).add(table[name])
    assert got["dot"] == {"ess.indexer", "ess.attend"}
    assert got["sine"] == {"ess.pool"}         # the innermost scope wins
    assert got["sort"] == {"unscoped"}


def test_name_gap_takes_the_innermost_span_covering_most_of_it():
    spans = [Interval("bench.step", 0, 101), Interval("ess.round", 0, 100),
             Interval("ess.fetch", 10, 60), Interval("ess.commit", 60, 90)]
    assert scopes.name_gap((20, 50), spans) == "ess.fetch"
    assert scopes.name_gap((55, 75), spans) == "ess.commit"
    # split evenly between two stages: the round covers most of it
    assert scopes.name_gap((40, 80), spans) == "ess.round"
    assert scopes.name_gap((100, 101), spans) == "bench.step"
    assert scopes.name_gap((200, 300), spans) == "host"


def _synthetic():
    ops = [(Interval("fusion.1", 10, 30), "jit_decode_round"),
           (Interval("sort.2", 30, 40), "jit_decode_round"),
           (Interval("fusion.9", 40, 45), "jit_decode_round"),
           (Interval("fusion.1", 70, 90), "jit_prefill_chunk")]
    mods = [Interval("jit_decode_round", 10, 45),
            Interval("jit_prefill_chunk", 70, 90)]
    spans = [Interval("bench.window", 0, 100),
             Interval("ess.round", 0, 100), Interval("ess.launch", 2, 8),
             Interval("ess.fetch", 8, 60), Interval("ess.commit", 60, 68)]
    maps = {"jit_decode_round": {"fusion.1": "ess.attend",
                                 "sort.2": "ess.topk"}}
    return scopes.Trace(ops, mods, spans), maps


def test_scope_seconds_read_the_decode_module_alone():
    trace, maps = _synthetic()
    dev = scopes.scope_seconds(trace, maps)
    assert dev["scopes"] == {"ess.attend": pytest.approx(20e-9),
                             "ess.topk": pytest.approx(10e-9)}
    assert dev["unmapped_ops"] == 1
    assert dev["unmapped_s"] == pytest.approx(5e-9)
    assert dev["top_ops"] == {"ess.attend": [["fusion.1", 20e-9]],
                              "ess.topk": [["sort.2", 10e-9]]}
    assert dev["total_s"] == pytest.approx(35e-9)
    assert dev["module_runs"] == 1


def test_a_loop_op_and_its_body_are_counted_once():
    """A ``while`` op's event spans the ops of its body: each instant goes
    to the innermost op, the loop keeps the time between its body's ops,
    and the scopes add up to the module's busy time."""
    ops = [(Interval("while.3", 10, 50), "jit_decode_round"),
           (Interval("fusion.4", 12, 30), "jit_decode_round"),
           (Interval("fusion.5", 32, 48), "jit_decode_round"),
           (Interval("copy.6", 40, 45), "jit_decode_round"),
           (Interval("fusion.7", 50, 60), "jit_decode_round")]
    mods = [Interval("jit_decode_round", 10, 60)]
    spans = [Interval("bench.window", 0, 100)]
    maps = {"jit_decode_round": {"while.3": "ess.topk",
                                 "fusion.4": "ess.topk",
                                 "fusion.5": "ess.topk",
                                 "copy.6": "unscoped",
                                 "fusion.7": "ess.pool"}}
    dev = scopes.scope_seconds(scopes.Trace(ops, mods, spans), maps)
    assert dev["scopes"] == {"ess.topk": pytest.approx(35e-9),
                             "unscoped": pytest.approx(5e-9),
                             "ess.pool": pytest.approx(10e-9)}
    assert dev["total_s"] == pytest.approx(50e-9)
    assert dev["top_ops"]["ess.topk"] == [["fusion.4", pytest.approx(18e-9)],
                                          ["fusion.5", pytest.approx(11e-9)],
                                          ["while.3", pytest.approx(6e-9)]]


def test_idle_gaps_and_round_host_time():
    trace, _ = _synthetic()
    idle = scopes.idle_gaps(trace)
    # gaps (0, 10), (45, 70), (90, 100): the prefill module's ops count
    # as busy too
    assert idle["by_span"] == {"ess.launch": pytest.approx(10e-9),
                               "ess.fetch": pytest.approx(25e-9),
                               "ess.round": pytest.approx(10e-9)}
    assert idle["idle_s"] == pytest.approx(45e-9)
    assert idle["longest"][0] == ["ess.fetch", pytest.approx(25e-9)]
    # cut at span edges: (0, 2) round, (2, 8) launch, (8, 10) fetch,
    # (45, 60) fetch, (60, 68) commit, (68, 70) and (90, 100) round
    assert idle["split"] == {"ess.round": pytest.approx(14e-9),
                             "ess.launch": pytest.approx(6e-9),
                             "ess.fetch": pytest.approx(17e-9),
                             "ess.commit": pytest.approx(8e-9)}
    # the round lasts 100 ns, 52 of them waiting in the fetch
    assert scopes.round_host_ms(trace) == pytest.approx(48e-6)
    # the decode run ends at 45, its fetch returns at 60
    assert scopes.fetch_slack_ms(trace) == pytest.approx(15e-6)


def test_recorded_trace_without_program_spans_reduces_as_before():
    """The v5e trace taken before the program named its stages: gaps are
    named by the harness's spans exactly as trace_reduce names them."""
    path = os.path.join(DATA, "v5e_small.xplane.pb")
    trace = scopes.load(path)
    assert not [s for s in trace.spans if s.name.startswith("ess.")]
    assert scopes.round_host_ms(trace) is None
    idle = scopes.idle_gaps(trace)
    red = tr.reduce_events(*tr.load(path))
    assert idle["idle_s"] == pytest.approx(red.window_s - red.busy_s)
    assert [n for n, _ in idle["longest"]] == [n for n, _ in red.idle_gaps]
    assert set(idle["split"]) <= {"bench.step", "bench.wait", "host"}
    for (_, a), (_, b) in zip(idle["longest"], red.idle_gaps):
        assert a == pytest.approx(b)
    dev = scopes.scope_seconds(trace, {}, module="jit__lambda")
    assert dev["module_runs"] == 4
    assert dev["total_s"] == pytest.approx(red.busy_s, rel=0.01)


def test_recorded_chip_trace_with_program_spans():
    """Six decode rounds of the tiny cell traced on one TPU v5e
    (``bench/stage_trace.py --tiny``), cut to the window's first six
    rounds, the device's ``XLA Ops``/``XLA Modules`` lines and the host's
    main thread, with event names cut to the instruction name; beside it
    the scope map of the instructions that ran."""
    import json
    trace = scopes.load(os.path.join(DATA, "ess_tiny.xplane.pb.gz"))
    with open(os.path.join(DATA, "ess_tiny_scopes.json")) as f:
        maps = json.load(f)
    red = scopes.reduce(trace, maps)
    dev = red["device"]
    assert dev["module_runs"] == 6 and dev["unmapped_ops"] == 0
    # each instant once: the six ``conditional`` ops of the head (3.43 us)
    # span the unscoped copies of their bodies, which keep that time
    assert dev["total_s"] == pytest.approx(8.49706e-3, rel=1e-6)
    assert sum(dev["scopes"].values()) == pytest.approx(dev["total_s"])
    want = {"ess.pool": 4.856867e-3, "ess.miss_gather": 1.9601e-3,
            "ess.topk": 1.195232e-3, "ess.spill": 1.63532e-4,
            "ess.attend": 1.17088e-4, "ess.ffn": 1.0004e-4,
            "ess.indexer": 5.1567e-5, "unscoped": 2.516e-05,
            "ess.head": 1.9843e-5, "ess.embed": 7.631e-6}
    assert dev["scopes"] == {k: pytest.approx(v, rel=1e-6)
                             for k, v in want.items()}
    idle = red["idle"]
    assert idle["idle_s"] == pytest.approx(1.1798327e-2, rel=1e-6)
    assert next(iter(idle["by_span"])) == "ess.fetch"
    assert idle["by_span"]["ess.fetch"] > 0.99 * idle["idle_s"]
    assert all(n.startswith("ess.") for n, _ in idle["longest"])
    assert red["round_host_ms"] == pytest.approx(0.975267, rel=1e-6)
