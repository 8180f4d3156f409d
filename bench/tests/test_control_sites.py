"""``bench/control_sites.py`` on four virtual devices: the planted fault
reaches the ``shard_map`` refresh, which ``bench/control.py``'s plant
does not."""
import json
import os
import subprocess
import sys

from conftest import ROOT

CODE = """
import json, sys
sys.path.insert(0, {root!r}); sys.path.insert(0, {tests!r})
from conftest import spec_with_four_sites, tiny_config
from bench import control_sites, run as harness
harness.start_jax(4, require_chip=False)
cell = harness.Cell("kdd99-4site.ingest", 2**31 + 5, spec=spec_with_four_sites(),
                    config=tiny_config("kdd99-4site"))
cell.setup()
sound = cell.session.engine._gathered_program()
line = control_sites.readings(cell, 1.5)
line.update(control_sites.planted_readings(cell, "seeding", 1.0))
line["retraced"] = cell.session.engine._gathered_program() is not sound
cell.close()
print(json.dumps(line))
"""


def test_the_plant_reaches_the_shard_map_refresh():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = CODE.format(root=str(ROOT), tests=str(ROOT / "bench" / "tests"))
    p = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    got = json.loads(p.stdout.strip().splitlines()[-1])
    assert got["retraced"]
    assert got["window"]["refreshes"] > 0
    assert got["window"]["ingest_pts_per_s"] > 0
    for key in ("program", "control", "seeding"):
        assert set(got[key]) >= {"thr_ulps", "center_ulps", "fed_gap"}
    # the sound refresh sits where one more Lloyd step leaves it; the
    # seeding alone does not
    assert got["seeding"]["center_ulps"] > 10 * got["program"]["center_ulps"]
    assert got["warm_periods"] >= 2
