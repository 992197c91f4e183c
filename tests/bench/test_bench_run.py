"""A whole run of a tiny cell on the CPU, the harness's look for a chip
skipped: a sound run is correct, and a run whose timed path is broken
underneath (a decode step that leaves its state unchanged; a token altered
where it is produced; crt3 with its voting off) is not.  The control (the
reference at int4, the precision below the int8 datapath that crt3 states)
fails the crt3 cell's limit through the harness's own comparison, on the
sample a run checked (``bench/study.py``).

No batch mean is taken in a serving cell (no training), so that fault does
not exist here.  These cells run on one chip; the exchange between chips
left out is caught in a cell sharded over four devices
(``test_bench_mesh.py``)."""
import time

import jax
import pytest

import tiny_cells
from bench import harness, study

SEED = 2**31 + 77


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny_cells.make_root(tmp_path_factory.mktemp("tiny"))


@pytest.fixture(scope="module")
def run():
    return tiny_cells.run_module()


def _run(run, root, name, seconds=0.5, trace=False):
    w = harness.cell(name, root)
    lines = []
    res, _ = run.run_cell(w, SEED, seconds, trace, jax.devices(), peak=None,
                          t_start=time.perf_counter(), root=root,
                          log=lines.append)
    return res, lines


def test_sound_run_is_correct(run, root):
    res, lines = _run(run, root, "tiny-clean")
    assert res["correct"] and res["failed"] == 0 and res["attempted"] >= 8
    assert list(res)[-1] == "check"
    assert set(res["metrics"]) == {"gen_tokens_per_s", "tpot_p95_ms", "setup_s"}
    assert res["device"]["count"] == 1
    assert "compiles_in_window=0" in lines[1]


def test_token_altered_where_produced_is_caught(run, root, monkeypatch):
    build = harness.build_system

    def broken(conf, seed, devices):
        model, params, sched = build(conf, seed, devices)
        chunk = sched._chunk

        def altered(*a):
            caches, tok, pos, tstep, toks = chunk(*a)
            return caches, tok, pos, tstep, toks.at[:, 0].add(1)
        sched._chunk = altered
        return model, params, sched

    monkeypatch.setattr(harness, "build_system", broken)
    res, _ = _run(run, root, "tiny-clean")
    c = res["check"]["mean_logit_gap"]
    assert res["failed"] == 0 and not res["correct"]
    assert c["value"] > c["limit"]


def test_step_that_leaves_state_unchanged_is_caught(run, root, monkeypatch):
    from repro.models.model import Model
    step = Model.decode_step

    def stale(self, params, caches, token, pos, ftc=None):
        _, logits = step(self, params, caches, token, pos, ftc=ftc)
        return caches, logits

    monkeypatch.setattr(Model, "decode_step", stale)
    res, _ = _run(run, root, "tiny-clean")
    assert not res["correct"]


def test_crt3_sound_and_control_fails(run, root):
    w = harness.cell("tiny-crt3", root)
    line = study.read(run, w, SEED, 0.5, True, jax.devices(), root=root,
                      log=lambda s: None)
    assert line["correct"] and line["served_tokens"] > 0
    c = line["control_check"]["mean_logit_gap"]
    assert c["value"] > c["limit"]


def test_crt3_with_voting_off_is_caught(run, root):
    w = harness.cell("tiny-crt3", root)
    w["conf"] = dict(w["conf"], protection=dict(w["conf"]["protection"],
                                                policy="base"))
    line = study.read(run, w, SEED, 0.5, False, jax.devices(), root=root,
                      log=lambda s: None)
    c = line["check"]["mean_logit_gap"]
    assert line["failed"] == 0 and not line["correct"]
    assert c["value"] > c["limit"]


def test_no_tpu_exits_nonzero(run, capsys):
    assert run.main(["--workload", "danube-clean-chat", "--seed", "1",
                     "--seconds", "1", "--trace", "0"]) == 2
    out = capsys.readouterr()
    assert out.out == "" and "TPU" in out.err
