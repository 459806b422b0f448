"""Child of ``test_chip_smoke``: ``chip_smoke``'s mesh phases on 4 forced
host devices at a small R-MAT scale, kernels in interpret mode.

Usage: python tests/_chip_smoke_mesh_child.py SCALE
"""

import os
import sys

import jax

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import chip_smoke  # noqa: E402


def main() -> None:
    scale = int(sys.argv[1])
    n_devices = len(jax.devices())
    wl = chip_smoke.build_workload(scale)
    refs = chip_smoke.build_references(wl, wcc_pagerank=False)
    phases = chip_smoke.mesh_phases(
        wl, refs, n_devices, backend="pallas-interpret"
    )
    for name, fn in phases:
        rec = chip_smoke.run_phase(name, fn, jax.devices())
        assert rec["correct"], rec.get("error", rec)
        assert rec["relayout_at_superstep"] > 0, rec
        assert rec["device_of_part"][0] != rec["device_of_part"][1], rec
    print("CHIP_SMOKE_MESH_OK")


if __name__ == "__main__":
    main()
