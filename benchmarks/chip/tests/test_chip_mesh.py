"""A four-chip cell on four forced host devices (a process of its own,
``mesh_child.py``): the warm-up and every session run on a ``MeshEngine``
over exactly the cell's devices, the window compiles nothing, and the
answers pass the check."""

import json
import os
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))


def test_a_four_chip_cell_runs_on_its_mesh(tmp_path):
    out = tmp_path / "mesh.json"
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(HERE, "mesh_child.py"), "4",
         str(out)], capture_output=True, text=True, timeout=900, env=env)
    assert proc.returncode == 0, (proc.stdout[-2000:], proc.stderr[-4000:])
    doc = json.loads(out.read_text())
    assert len(doc["devices"]) == 4
    # the warm-up's engine, the warm-up session's and each window session's
    assert len(doc["engines"]) == 2 + doc["sessions"]
    assert set(doc["engines"]) == {"MeshEngine"}
    assert all(sorted(m) == sorted(doc["devices"]) for m in doc["meshes"])
    assert doc["subset"] == ["MeshEngine", doc["devices"][:2]]
    assert doc["window_compiles"] == 0, doc["compiled"]
    assert doc["failed"] == 0 and doc["rounds"] > 0
    assert doc["correct"], doc["checks"]
