"""Compile each cell's train step for a described TPU v5e, without a chip.

    JAX_PLATFORMS=cpu python3 perfbench/compile_check.py [workload ...]

Builds the program's step exactly as a run does, on the devices of a
described ``v5e:2x2`` topology, and prints the compiler's peak device
memory (``memory_analysis().peak_memory_in_bytes``): what the compiler
refuses here costs no chip time.  Nothing runs, so nothing is timed.
"""

from __future__ import annotations

import json
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def main() -> None:
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies

    from perfbench.lib import spec
    from perfbench.lib.program import Program

    jax.config.update("jax_enable_compilation_cache", False)
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    names = sys.argv[1:] or [w["name"] for w in bench["workloads"]]
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    for name in names:
        cell = spec.load(name, bench)
        prog = Program(cell, topo.devices[:cell.chips])
        print(json.dumps({"workload": name, "chips": cell.chips,
                          "compiler_peak_bytes": prog.hbm_peak_bytes}), flush=True)


if __name__ == "__main__":
    main()
