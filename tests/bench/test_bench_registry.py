"""The benchmark finds every piece by name, and a new cell is only files."""

import json
import re
import shutil

import pytest

from bench import harness as H

BM = H.load_benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
CELLS = [w["name"] for w in BM["workloads"]]


def test_top_level_keys():
    assert set(BM) == {"command", "paths", "run_seconds", "configs",
                       "workloads", "end_to_end", "per_layer"}
    assert BM["command"][:2] == ["python3", "bench/run.py"]
    assert 1 <= BM["run_seconds"] <= 51
    for p in BM["paths"]:
        assert (H.ROOT / p).is_dir() and not p.startswith("/")


def test_names_units_and_bounds():
    names = [m["name"] for m in BM["end_to_end"] + BM["per_layer"]]
    names += CELLS + [c["name"] for c in BM["configs"]]
    assert len(names) == len(set(names))
    for n in names:
        assert NAME.match(n), n
    for m in BM["end_to_end"] + BM["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
    setup = [m for m in BM["end_to_end"] if m["name"] == "setup_s"]
    assert setup and setup[0]["bound"] <= 0.25
    for m in BM["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25, m
        assert m["source"] in ("host_clock", "device_trace")
    for m in BM["per_layer"]:
        assert m["moves"] in {e["name"] for e in BM["end_to_end"]}
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves_every_piece(cell):
    w, centry = H.find_cell(BM, cell)
    assert w["chips"] in (1, 4)
    cfg = H.load_config(centry)
    assert cfg["name"] == centry["name"]
    assert centry["file"].startswith("bench/configs/")
    traffic = H.load_traffic(w["traffic"])
    driver = H.load_driver(traffic["driver"])
    assert callable(driver.run)
    e2e = H.metrics_of(BM, cell, "end_to_end")
    assert "setup_s" in [m["name"] for m in e2e] and len(e2e) >= 2
    layers = H.metrics_of(BM, cell, "per_layer")
    assert layers
    for m in layers:
        assert callable(H.load_metric(m["name"]).read)
        assert m["moves"] in [e["name"] for e in e2e]


def test_every_config_is_used():
    used = {w["config"] for w in BM["workloads"]}
    assert used == {c["name"] for c in BM["configs"]}


def test_new_cell_is_found_from_new_files_alone(tmp_path):
    """A later change adds a traffic mix, a metric and their entries; the
    harness finds them without any existing file being edited."""
    root = tmp_path / "checkout"
    shutil.copytree(H.BENCH, root / "bench")
    bm = json.loads(json.dumps(BM))
    bench = root / "bench"
    waves = H.load_traffic("tablev-waves")
    (bench / "traffic" / "tablev-sew8.json").write_text(json.dumps(
        dict(waves, sews=[8])))
    (bench / "metrics" / "nmc.queue_ms.py").write_text(
        "def read(ctx):\n    return 1.5\n")
    bm["workloads"].append({"name": "nmc-edge.tablev-sew8",
                            "config": "nmc-edge",
                            "traffic": "tablev-sew8", "chips": 1, "why": "x"})
    bm["per_layer"].append({"name": "nmc.queue_ms", "unit": "ms",
                            "better": "lower", "source": "host_clock",
                            "layer": "nmc dispatch (host)",
                            "moves": "nmc_kernels_per_s"})
    for m in bm["end_to_end"]:
        if "nmc-edge.tablev-waves" in m.get("workloads", []):
            m["workloads"].append("nmc-edge.tablev-sew8")
    (root / "BENCHMARK.json").write_text(json.dumps(bm))

    bm2 = H.load_benchmark(root)
    w, centry = H.find_cell(bm2, "nmc-edge.tablev-sew8")
    assert H.load_config(centry, root)["n_tiles"] == 16
    assert H.load_traffic(w["traffic"], bench)["sews"] == [8]
    layers = H.metrics_of(bm2, "nmc-edge.tablev-sew8", "per_layer")
    assert "nmc.queue_ms" in [m["name"] for m in layers]
    got = H.run_metric_readers(
        layers, None, loader=lambda n: H.load_metric(n, bench)
        if n == "nmc.queue_ms" else _Silent)
    assert got == {"nmc.queue_ms": {"value": 1.5, "unit": "ms"}}


class _Silent:
    @staticmethod
    def read(ctx):
        return None


def test_unknown_device_has_no_peaks():
    with pytest.raises(KeyError):
        H.load_peaks("TPU v9 imaginary")
    assert H.load_peaks("TPU v5 lite")["int8_ops_per_s"] == 393e12


def test_percentile_and_mean():
    assert H.percentile([], 95) is None
    assert H.percentile([5.0], 95) == 5.0
    assert H.percentile(list(range(101)), 95) == pytest.approx(95.0)
    assert H.percentile([0, 10], 50) == pytest.approx(5.0)
    assert H.mean([1, 2, 3]) == 2
