"""A toy deployment added to a temporary copy of the benchmark's tree, as
a ``model_config`` PR adds one: files and entries, nothing edited."""

import json
import os
import shutil

from benchmark import deployment, kinds, run


def add_kind(tmp_path, monkeypatch, toy) -> None:
    """The benchmark's data directories copied as they are into
    ``tmp_path``, and the files of ``toy`` (a module with the texts
    ``KIND``, ``CONFIG``, ``TRAFFIC`` and the entries ``CONFIG_ENTRY``,
    ``CELL``) added beside them; the harness is pointed there for the
    test's length."""
    for sub in ("configs", "traffic", "layers"):
        shutil.copytree(os.path.join(deployment.HERE, sub), tmp_path / sub)
    (tmp_path / "kinds").mkdir()
    (tmp_path / "kinds" / f"{toy.CONFIG['kind']}.py").write_text(toy.KIND)
    (tmp_path / "configs" / f"{toy.CONFIG['name']}.json").write_text(
        json.dumps(toy.CONFIG))
    (tmp_path / "traffic" / f"{toy.CELL['traffic']}.json").write_text(
        json.dumps(toy.TRAFFIC))
    bench = run.load_benchmark()
    bench["configs"].append(toy.CONFIG_ENTRY)
    bench["workloads"].append(toy.CELL)
    monkeypatch.setattr(deployment, "HERE", str(tmp_path))
    monkeypatch.setattr(kinds, "__path__",
                        [*kinds.__path__, str(tmp_path / "kinds")])
    monkeypatch.setattr(run, "load_benchmark", lambda: bench)


def rehearse(capsys, cell: str, *extra) -> tuple[dict, dict]:
    """One rehearsal of ``cell`` through ``run.main`` (the CPU, 1.5 s):
    (the result's line, the ``info`` line)."""
    assert run.main(["--workload", cell, "--seconds", "1.5", "--trace", "0",
                     "--rehearse", *extra]) == 0
    cap = capsys.readouterr()
    info = next(json.loads(line)["info"] for line in cap.err.splitlines()
                if line.startswith('{"info"'))
    return json.loads(cap.out.strip().splitlines()[-1]), info
