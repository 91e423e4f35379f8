"""The four-chip mix ``dp4-k4-q8-s4096`` as its file states it (one worker
per device through the engine's shard_map round), under the one-chip cell's
configuration, on four virtual CPU devices at the system's tiny size: a
sound run is correct, and with the exchange between devices left out it is
not. The mix waits in ``traffic/`` for its cell (PERF.md §7)."""
import json
import os
import subprocess
import sys

from perfbench import spec

TRAFFIC = "dp4-k4-q8-s4096"

SCRIPT = """
import json, sys
import tiny_cells
res = tiny_cells.tiny(tiny_cells.ONE_CHIP[0], traffic=sys.argv[1])
assert res["traffic"]["placement"] == "mesh", res["traffic"]
sound = tiny_cells.run(res)
tiny_cells.plant("no_exchange", setattr)
broken = tiny_cells.run(res)
print(json.dumps({"sound": sound["correct"], "broken": broken["correct"],
                  "count": sound["device"]["count"],
                  "workers": res["traffic"]["workers"],
                  "checks": [sound["checks"], broken["checks"]]}))
"""


def test_mesh_cell_sound_and_exchange_left_out():
    here = os.path.dirname(os.path.abspath(__file__))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join(
                   [here, spec.ROOT, os.path.join(spec.ROOT, "src")]))
    p = subprocess.run([sys.executable, "-c", SCRIPT, TRAFFIC], env=env,
                       capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["count"] == 4 and out["workers"] == 4
    assert out["sound"] is True, out["checks"]
    assert out["broken"] is False, out["checks"]
