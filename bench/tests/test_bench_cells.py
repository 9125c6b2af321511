"""Every cell of ``BENCHMARK.json`` resolves, by name, to its files; the
file keeps to the benchmark's contract; and the command refuses to run
without a TPU."""
import json
import re
from pathlib import Path

import pytest

from bench import run

ROOT = Path(__file__).resolve().parents[2]
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.mark.parametrize("cell", [w["name"] for w in SPEC["workloads"]])
def test_cell_resolves_to_its_files(cell):
    got = run.resolve(cell)
    w = got["workload"]
    assert got["config"]["name"] == w["config"]
    assert (run.BENCH / f"{got['traffic']['kind']}.py").is_file()
    assert got["limits"]
    names = {m["name"] for m in got["end_to_end"]}
    assert "setup_s" in names and len(names) >= 2
    assert got["per_layer"]
    for m in got["per_layer"]:
        assert m["moves"] in names
        assert hasattr(run.load_module(got["readers"][m["name"]]), "read")


def test_benchmark_file_keeps_to_the_contract():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["paths"] == ["bench"]
    configs = {c["name"]: c for c in SPEC["configs"]}
    for c in SPEC["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert (ROOT / c["file"]).is_file() and c["file"].startswith("bench/")
    pairs = set()
    for w in SPEC["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
        assert len(w["why"]) <= 200
    metrics = SPEC["end_to_end"] + SPEC["per_layer"]
    assert len({m["name"] for m in metrics}) == len(metrics)
    for m in metrics:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    for m in SPEC["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in SPEC["per_layer"]:
        assert m["moves"] in {e["name"] for e in SPEC["end_to_end"]}
        if m["name"].endswith("_roofline"):
            assert (ROOT / "bench" / "work"
                    / f"{m['name'][:-len('_roofline')]}.py").is_file()


def test_refuses_to_run_without_a_tpu(capsys):
    cell = SPEC["workloads"][0]["name"]
    with pytest.raises(SystemExit) as exc:
        run.main(["--workload", cell, "--seed", "1", "--seconds", "1"])
    assert exc.value.code not in (0, None)
    assert capsys.readouterr().out == ""


def test_unknown_device_kind_has_no_peaks():
    with pytest.raises(KeyError):
        run.peaks_of("cpu")
    assert run.peaks_of("TPU v5 lite")["bf16_flops_per_s"] == 197e12
