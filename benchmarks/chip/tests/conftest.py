"""Test helpers for the chip benchmark: run as
`python -m pytest benchmarks/chip/tests` from the root of the repo.
Everything here runs on the CPU at small sizes; the chip's numbers come
only from the benchmark's runs on the chip."""
import copy
import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(ROOT / "src"))

TINY_KEYS = 4096


def tiny(cell_name: str, n: int = TINY_KEYS):
    """The cell of BENCHMARK.json, cut to `n` keys and operations and a
    ring of 8 requests, keeping its mix of reads and inserts."""
    import spec
    cell = copy.deepcopy(spec.load_cell(cell_name))
    tr = cell.traffic
    reads = tr["reads"] * n // cell.config["ops_per_request"]
    cell.config.update(n_keys=n, ops_per_request=n)
    cell.traffic = dict(tr, reads=reads, inserts=n - reads, ring=8)
    return cell
