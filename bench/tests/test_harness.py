"""``BENCHMARK.json`` against the files it names, and the harness's
refusal to run without a chip."""
import json
import os
import re

import pytest

from bench import run

ROOT = run.ROOT
with open(os.path.join(ROOT, "BENCHMARK.json")) as _fh:
    BENCH = json.load(_fh)
CELLS = [w["name"] for w in BENCH["workloads"]]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


@pytest.mark.parametrize("cell", CELLS)
def test_cell_files_and_metrics(cell):
    spec = run.load_cell(cell)
    assert os.path.isfile(os.path.join(
        ROOT, "bench", "drivers", spec["config"]["driver"] + ".py"))
    assert isinstance(spec["config"]["rehearsal"], dict)
    e2e = {m["name"] for m in spec["end_to_end"]}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert spec["per_layer"], cell
    for m in spec["per_layer"]:
        assert m["moves"] in e2e, (cell, m["name"])
    assert spec["config"]["chips"] == spec["cell"]["chips"]


def test_every_reader_and_name():
    names = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    names += CELLS + [c["name"] for c in BENCH["configs"]]
    assert len(set(names)) == len(names)
    assert all(NAME.match(n) for n in names)
    for m in BENCH["per_layer"]:
        assert os.path.isfile(os.path.join(
            ROOT, "bench", "metrics", m["name"] + ".py")), m["name"]
        assert set(m["workloads"]) <= set(CELLS)
    for c in BENCH["configs"]:
        assert os.path.isfile(os.path.join(ROOT, c["file"]))
        assert c["file"].startswith("bench/")


def test_bounds():
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25, m["name"]


def test_unknown_workload_refused():
    with pytest.raises(run.Refused, match="no workload"):
        run.load_cell("no-such-cell")


def test_no_tpu_exits_nonzero_without_a_result(capsys):
    """Here JAX runs on the CPU: the harness refuses, prints nothing on
    standard output, and never falls back."""
    rc = run.main(["--workload", CELLS[0], "--seed", "2147483659",
                   "--seconds", "1", "--trace", "0"])
    out, err = capsys.readouterr()
    assert rc == 2
    assert out == ""
    assert "no TPU" in err


def test_no_program_exits_nonzero(tmp_path, capsys, monkeypatch):
    """A checkout holding only BENCHMARK.json and bench/ has no program."""
    monkeypatch.setattr(run, "ROOT", str(tmp_path))
    rc = run.main(["--workload", CELLS[0], "--seed", "1",
                   "--seconds", "1"])
    out, err = capsys.readouterr()
    assert rc == 2 and out == "" and "no program" in err
