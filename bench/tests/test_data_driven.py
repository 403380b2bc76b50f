"""The harness finds cells by name: a new cell is new files and entries.

In a temporary copy of the benchmark, a new configuration (with its
reference beside it), a new traffic mix, a new per-layer metric and a
new cell are written as files and ``BENCHMARK.json`` entries; the harness
runs the cell without an edit to any file the benchmark already had.
"""
import hashlib
import json
import shutil

from conftest import BENCH, ROOT, run_cell


def _digests(root):
    return {p.relative_to(root): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in sorted((root / "bench").rglob("*")) if p.is_file()
            and "__pycache__" not in p.parts}


def _copy(tmp_path, with_program=True):
    root = tmp_path / "checkout"
    shutil.copytree(BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(ROOT / "BENCHMARK.json", root / "BENCHMARK.json")
    if with_program:
        (root / "src").symlink_to(ROOT / "src")
    return root


def test_new_cell_from_new_files_only(tmp_path):
    root = _copy(tmp_path)
    before = _digests(root)
    cfg = json.loads((BENCH / "configs" / "arria10-gx900-fleet.json")
                     .read_text())
    cfg.update(name="tiny-fleet", fsm_slices=3, blocks_per_slice=1,
               rehearsal={"fsm_slices": 3, "blocks_per_slice": 1})
    (root / "bench/configs/tiny-fleet.json").write_text(json.dumps(cfg))
    shutil.copy(BENCH / "configs" / "arria10-gx900-fleet_ref.py",
                root / "bench/configs/tiny-fleet_ref.py")
    (root / "bench/traffic/mul8-shared.json").write_text(json.dumps(
        {"kind": "grid_ops", "op": "mac", "per_slot": False,
         "bits": {"dist": "fixed", "value": 8}}))
    (root / "bench/metrics/dispatches_in_window.py").write_text(
        "def read(run):\n    return float(run.facts['dispatches'])\n")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({
        "name": "tiny-fleet", "source": "https://arxiv.org/abs/2203.12521",
        "file": "bench/configs/tiny-fleet.json", "reduced": [],
        "why": "test"})
    bench["workloads"].append({
        "name": "tiny.mul8", "config": "tiny-fleet",
        "traffic": "mul8-shared", "chips": 1, "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "fleet.mul16-shared" in m.get("workloads", []):
            m["workloads"].append("tiny.mul8")
    bench["per_layer"].append({
        "name": "dispatches_in_window", "unit": "dispatches",
        "better": "higher", "source": "host_clock", "layer": "device",
        "moves": "sim_lane_cycles_per_s", "workloads": ["tiny.mul8"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    rc, res, err = run_cell(root, "tiny.mul8", seconds=1)
    assert rc == 0, err[-3000:]
    assert res["correct"] and "sim_lane_cycles_per_s" in res["metrics"]
    rc, res, err = run_cell(root, "tiny.mul8", "--trace", "1", seconds=1)
    assert rc == 0, err[-3000:]
    assert res["metrics"]["dispatches_in_window"]["value"] >= 1
    after = _digests(root)
    assert {k: v for k, v in after.items() if k in before} == before


def test_benchmark_files_alone_do_not_run(tmp_path):
    """A directory with only BENCHMARK.json and the benchmark's files has
    no program: the harness exits non-zero and prints no result."""
    root = _copy(tmp_path, with_program=False)
    rc, res, _ = run_cell(root, "fleet.mul16-shared", seconds=1)
    assert rc != 0 and res is None
