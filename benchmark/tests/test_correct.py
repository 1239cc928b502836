"""``correct`` has to be able to come out false.

Each test skips the harness's look for a chip (``--rehearse``: the CPU,
a small deployment) and drives the rest of a run through ``run.main``:
the sound program reads ``correct`` true; the control (the program given
twice the nominal quota the configuration states) and each fault planted
under the timed path read it false, by the number named.

Faults a cell of this system can have: a step that returns its state
unchanged (a scheduler that seats nobody) and an answer altered where it
is produced (the device's plan, before verify-and-commit). There is no
batch mean and, on one chip, no exchange between chips.
"""

import json

import numpy as np
import pytest

from benchmark import deployment, run
from benchmark.kinds import flat

SMALL = ["--workload", "large-scale-replay", "--seconds", "4", "--trace",
         "0", "--rehearse", "--cohorts", "2", "--cqs-per-cohort", "8"]


def drive(capsys, *extra) -> dict:
    assert run.main(SMALL + list(extra)) == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def compared(result: dict) -> dict:
    return {k: c["value"] for k, c in result["compared"].items()}


def test_sound_run_is_correct(capsys):
    r = drive(capsys, "--seed", "2147483659")
    assert r["correct"], r["compared"]
    assert list(r)[-2] == "compared"  # last but the rehearsal's label
    assert r["attempted"] > 0 and r["failed"] == 0
    assert set(r["metrics"]) == {"adm_per_s", "pass_s", "tta_top_p95_s",
                                 "setup_s"}


@pytest.mark.parametrize("seed", [3, 4, 5])
def test_control_double_nominal_is_not_correct(capsys, seed):
    r = drive(capsys, "--seed", str(seed), "--control", "double_nominal")
    assert not r["correct"]
    c = compared(r)
    assert c["over_quota"] > 0
    # the program itself was sound on the deployment it was given
    assert c["ghosts"] == 0 and c["solver_plan_fallbacks_total"] == 0


def test_state_unchanged_is_not_correct(capsys, monkeypatch):
    from kueue_oss_tpu.scheduler.scheduler import Scheduler

    real = Scheduler.run_until_quiet

    def seats_nobody(self, *a, **kw):
        if self.solver is None:       # the twin stays sound
            return real(self, *a, **kw)
        return 0

    monkeypatch.setattr(Scheduler, "run_until_quiet", seats_nobody)
    r = drive(capsys, "--seed", "6")
    assert not r["correct"]
    assert compared(r)["starved"] > 0


def test_altered_plan_is_not_correct(capsys, monkeypatch):
    from kueue_oss_tpu.solver.engine import SolverEngine

    real = SolverEngine._local_solve

    def seats_everybody(self, problem, frame, **kw):
        out = list(real(self, problem, frame, **kw))
        admitted = np.array(out[0])
        admitted[:problem.n_workloads] = True
        out[0] = admitted
        out[1] = np.maximum(np.asarray(out[1]), 0)    # a flavor option
        out[2] = np.maximum(np.asarray(out[2]), 0)    # an admission round
        return tuple(out)

    monkeypatch.setattr(SolverEngine, "_local_solve", seats_everybody)
    r = drive(capsys, "--seed", "7")
    assert not r["correct"]
    c = compared(r)
    assert c["solver_plan_fallbacks_total"] + c["over_quota"] > 0


def books():
    cfg = flat.scaled(deployment.load_config("upstream-baseline"), 1, 2, 50)
    arrivals = flat.schedule(cfg, 1)
    return cfg, arrivals, {a.klass + a.cq[-1]: a.key for a in arrivals
                           if a.key.endswith("-0")}


def one(events=(), added=(), removed=()):
    return {"events": list(events), "added": list(added),
            "removed": list(removed)}


def test_reference_counts_each_guarantee():
    cfg, arrivals, k = books()
    keys = [a.key for a in arrivals]

    def audit(*passes):
        return flat.audit(cfg, arrivals, keys, passes)["counts"]

    # 2 queues x 20 cpu: two larges fill the cohort; nothing else fits
    full = one(added=[k["large0"], k["large1"]])
    assert not any(audit(full).values())
    # a third workload on top: the cohort is over its 40
    assert audit(full, one(added=[k["small0"]]))["over_quota"] == 1
    # nobody seated although everything would fit, nominal quota idle
    got = audit(one())
    assert got["starved"] == 2 and got["below_nominal"] == 2
    # a small seated while a large of its queue waits and would fit
    got = audit(one(added=[k["small0"], k["large1"]]))
    assert got["inversions"] == 1
    # a large loses its seat to nobody
    assert audit(full, one(removed=[k["large0"]]))["bad_evictions"] == 1
    # ... to a finish: lawful (and then its queue's next has to come)
    fin = one(events=[("finish", k["large0"], 1.0)],
              removed=[k["large0"]], added=[k["medium0"]])
    assert audit(full, fin)["bad_evictions"] == 0
    # a reservation for one that holds one already
    assert audit(full, one(added=[k["large0"]]))["ghosts"] == 1
