"""BENCHMARK.json against the benchmark contract's form, and the harness
finding everything by name: a cell added by new files and one entry."""
import json
import re
import shutil
import time

import pytest

from bench import manifest

MAN = manifest.load_manifest()
ROOT = manifest.ROOT
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
E2E = {m["name"]: m for m in MAN["end_to_end"]}
CELLS = [w["name"] for w in MAN["workloads"]]


def test_top_level_keys():
    assert set(MAN) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    assert MAN["command"] == ["python3", "bench/run.py"]
    assert MAN["paths"] == ["bench"]
    assert 1 <= MAN["run_seconds"] <= 51
    assert len(json.dumps(MAN)) < 64 * 1024


def test_names_units_and_text():
    names = [c["name"] for c in MAN["configs"]] + CELLS + \
        [m["name"] for m in MAN["end_to_end"] + MAN["per_layer"]]
    assert len(names) == len(set(names))
    for n in names:
        assert NAME.match(n), n
    for c in MAN["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        for k in c["reduced"]:
            assert NAME.match(k), k
    for w in MAN["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["traffic"]) and w["chips"] in (1, 4)
    for m in MAN["end_to_end"] + MAN["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
    texts = [c["why"] for c in MAN["configs"]] + \
        [c["source"] for c in MAN["configs"]] + \
        [w["why"] for w in MAN["workloads"]] + \
        [m["layer"] for m in MAN["per_layer"]]
    for t in texts:
        assert 1 <= len(t) <= 200 and "\t" not in t and "\n" not in t, t


def test_bounds():
    assert "setup_s" in E2E
    for m in MAN["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25, m


def test_each_metric_moves_what_its_cells_report():
    for m in MAN["per_layer"]:
        assert m["moves"] in E2E, m
        e2e = E2E[m["moves"]]
        for cell in m.get("workloads", CELLS):
            assert cell in CELLS
            assert cell in e2e.get("workloads", CELLS), (m["name"], cell)


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_reports_enough(cell):
    c = manifest.find_cell(cell)
    names = {m["name"] for m in c.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    assert c.per_layer


def test_four_chip_share():
    four = sum(1 for w in MAN["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(MAN["workloads"]) // 2)


def test_named_files_exist():
    for c in MAN["configs"]:
        assert c["file"].startswith("bench/")
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert cfg["name"] == c["name"]
        assert (ROOT / "bench" / "reference" / f"{cfg['reference']}.py").exists()
        assert set(c["reduced"]) == set(cfg["reduced"])
    for w in MAN["workloads"]:
        assert (ROOT / "bench" / "traffic" / f"{w['traffic']}.json").exists()
    for m in MAN["end_to_end"] + MAN["per_layer"]:
        assert callable(manifest.metric_reader(m["name"]))


def test_cell_added_by_files_and_one_entry(tmp_path):
    """A new traffic file and one workloads entry make a cell that the
    harness finds and runs, with no existing file edited."""
    from bench import run as bench_run
    from bench.tests.tiny import tiny_cell
    import jax

    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    man = json.loads(json.dumps(MAN))
    burst = json.loads((ROOT / "bench" / "traffic" / "chat.json").read_text())
    burst["rate_per_s"] = 3.0
    (tmp_path / "bench" / "traffic" / "chat-fast.json").write_text(
        json.dumps(burst))
    man["workloads"].append({"name": "olmo1b.chat-fast", "config": "olmo-1b",
                             "traffic": "chat-fast", "chips": 1,
                             "why": "a cell added as data"})
    for m in man["end_to_end"] + man["per_layer"]:
        if "olmo1b.chat" in m.get("workloads", ()):
            m["workloads"].append("olmo1b.chat-fast")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(man))

    cell = manifest.find_cell("olmo1b.chat-fast", tmp_path)
    assert cell.traffic["rate_per_s"] == 3.0
    assert {m["name"] for m in cell.end_to_end} == \
        {m["name"] for m in manifest.find_cell("olmo1b.chat").end_to_end}
    tiny = tiny_cell("olmo1b.chat-fast", root=tmp_path)
    res = bench_run.run(tiny, 9, 2.0, False, jax.devices()[:1], None,
                        t_start=time.perf_counter())
    assert res["correct"] and res["failed"] == 0
    assert set(res["metrics"]) == {"latency_p90_s", "setup_s"}


def test_metric_reader_per_quantity():
    """A metric named ``<quantity>.<kind>`` reads with the quantity's
    reader; every reader file serves some metric of the manifest."""
    readers = {p.stem for p in (ROOT / "bench" / "metrics").glob("*.py")}
    used = set()
    for m in MAN["end_to_end"] + MAN["per_layer"]:
        name = m["name"]
        used.add(name if name in readers else name.rsplit(".", 1)[0])
    assert used == readers
    read = manifest.metric_reader("engine.tick_ms.a_later_kind")
    assert read.__code__.co_filename.endswith("engine.tick_ms.py")
    with pytest.raises(FileNotFoundError):
        manifest.metric_reader("no_such_quantity.chat")
