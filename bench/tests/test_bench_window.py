"""What a run puts on the ``Window`` for the metric readers: every serve
counter the program keeps, and in a traced run each scope's device time a
round and the host's own time a round."""

import dataclasses
import gzip
import json
import os
import shutil
import tempfile
import types

import pytest

import run
import tiny
import trace_reduce

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
SEED = 2 ** 33 + 11
STAGES = ("miss_gather_ms.decode", "spill_ms.decode", "pool_ms.decode",
          "ffn_ms.decode", "attend_ms.decode", "indexer_ms.decode",
          "topk_ms.decode", "head_ms.decode")


def test_a_new_serve_counter_reaches_the_window():
    from repro.serving.engine import ServeReport

    @dataclasses.dataclass
    class Later(ServeReport):
        reused_id_rows: int = 0

    r = Later(rounds=3, h2d_rows=7, reused_id_rows=5)
    r.events.append("ev")
    eng = types.SimpleNamespace(session=types.SimpleNamespace(report=r))
    a = run.counters(eng, 2)
    r.rounds += 1
    r.reused_id_rows += 4
    d = run.counters(eng, 5).minus(a)
    assert (d.reused_id_rows, d.rounds, d.h2d_rows, d.steps) == (4, 1, 0, 3)
    assert a.n_events == 1 and a.h2d_rows == 7
    assert "wall_s" not in a and "finished_rids" not in a
    with pytest.raises(AttributeError):
        a.no_such_counter


def test_traced_run_puts_stage_times_on_the_window(monkeypatch):
    """The tiny cell traced on the CPU, whose trace has no TPU plane: the
    run maps its own decode program onto ``ess.*`` scopes, and reads the
    recorded chip trace of the same cell (``test_bench_scopes.py``) with
    its scope map in place of its own."""
    keep = tempfile.mkdtemp()
    rec = os.path.join(keep, "chip.xplane.pb")
    with gzip.open(os.path.join(DATA, "ess_tiny.xplane.pb.gz")) as f, \
            open(rec, "wb") as g:
        shutil.copyfileobj(f, g)
    with open(os.path.join(DATA, "ess_tiny_scopes.json")) as f:
        chip_maps = json.load(f)
    own, windows = {}, []
    real_maps, real_drive = run.stage_maps, run.drive_closed

    def stage_maps(eng):
        own.update(real_maps(eng))
        return chip_maps

    def drive(eng, reqs, w, tracer, SP):
        windows.append(w)
        return real_drive(eng, reqs, w, tracer, SP)

    monkeypatch.setattr(run, "stage_maps", stage_maps)
    monkeypatch.setattr(run, "drive_closed", drive)
    monkeypatch.setattr(trace_reduce, "newest", lambda d: rec)
    tmp = tempfile.mkdtemp()
    bench = tiny.make_tree(tmp)
    bench["per_layer"] = [{"name": n, "unit": "ms"} for n in
                          STAGES + ("round_host_ms.decode",
                                    "decode_round_ms.decode")]
    out = run.run_cell(bench, "tiny.closed", SEED, 0.5, True,
                       require_tpu=False, root=tmp, data=tmp)
    shutil.rmtree(keep)
    assert out["correct"], out["checks"]
    assert set(own) == {"jit_decode_round"}
    assert {"ess.topk", "ess.attend", "ess.pool"} <= \
        set(own["jit_decode_round"].values())
    w = windows[0]
    assert dict(out["breakdown"]["scopes"]) == w.scope_ms
    assert len(w.scope_ms) == 10
    got = {k: v["value"] for k, v in out["metrics"].items()}
    assert set(got) == set(STAGES) | {"round_host_ms.decode",
                                      "decode_round_ms.decode"}
    assert got["pool_ms.decode"] == w.scope_ms["ess.pool"]
    assert got["topk_ms.decode"] / got["pool_ms.decode"] == \
        pytest.approx(1.195232 / 4.856867, rel=1e-5)
    assert got["round_host_ms.decode"] == pytest.approx(0.975267, rel=1e-6)
    # the scopes add up to the device's busy time a round
    assert sum(w.scope_ms.values()) == \
        pytest.approx(got["decode_round_ms.decode"], rel=1e-6)
