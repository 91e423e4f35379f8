"""The one-chip cell's traffic placed one worker per device through the
engine's shard_map round (the four-chip deployment the cell is a share of)
on four virtual CPU devices at a tiny size: a sound run is correct, and
with the exchange between devices left out it is not."""
import json
import os
import subprocess
import sys

from perfbench import spec

SCRIPT = """
import json, sys
import tiny_cells
res = tiny_cells.tiny(tiny_cells.ONE_CHIP[0])
res["traffic"].update(placement="mesh", workers=4)
sound = tiny_cells.run(res)
tiny_cells.plant("no_exchange", setattr)
broken = tiny_cells.run(res)
print(json.dumps({"sound": sound["correct"], "broken": broken["correct"],
                  "count": sound["device"]["count"],
                  "checks": [sound["checks"], broken["checks"]]}))
"""


def test_mesh_cell_sound_and_exchange_left_out():
    here = os.path.dirname(os.path.abspath(__file__))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join(
                   [here, spec.ROOT, os.path.join(spec.ROOT, "src")]))
    p = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                       capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["count"] == 4
    assert out["sound"] is True, out["checks"]
    assert out["broken"] is False, out["checks"]
