"""The benchmark's own tests: run with `python -m pytest bench/tests` from
the repository root (CPU only; nothing here loads a TPU library while
modules are imported)."""
import json
import shutil
import sys
import time
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

DATA = Path(__file__).resolve().parent / "data"
CELL = "granite-moe-1b-a400m.offline-decode"


@pytest.fixture
def tiny_checkout(tmp_path):
    """Make a checkout of the benchmark at CPU size: every configuration of
    `BENCHMARK.json` is `data/<config>.json`, the cell's backlog keeps its
    shape with lengths a CPU serves in seconds, and the block families are
    the benchmark's, or the one that `data/<family>.py` adds."""
    def make(config: str, family: str = "") -> Path:
        from bench import traffic

        bench = json.loads((ROOT / "BENCHMARK.json").read_text())
        for c in bench["configs"]:
            c["file"] = str(DATA / f"{config}.json")
        (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
        mix = traffic.load_mix("offline-decode")
        mix["context_cap"] = 128
        mix["classes"] = [dict(mix["classes"][0],
                               user_len={"median": 24, "sigma": 0.5, "lo": 8, "hi": 64},
                               output_len={"median": 24, "sigma": 0.3, "lo": 16, "hi": 48})]
        (tmp_path / "bench" / "traffic").mkdir(parents=True)
        (tmp_path / "bench" / "traffic" / "offline-decode.json").write_text(json.dumps(mix))
        if family:
            (tmp_path / "bench" / "reference").mkdir()
            shutil.copy(DATA / f"{family}.py", tmp_path / "bench" / "reference")
        else:
            (tmp_path / "bench" / "reference").symlink_to(ROOT / "bench" / "reference")
        return tmp_path

    return make


@pytest.fixture
def run_tiny():
    """Run the cell once in a checkout, with no look for a chip."""
    def run(root: Path, seed: int) -> dict:
        from bench import run as bench_run

        args = bench_run.parse(["--workload", CELL, "--seed", str(seed),
                                "--seconds", "3", "--trace", "0"])
        return bench_run.run_cell(args, root=root, require_chip=False,
                                  t_process=time.perf_counter())

    return run
