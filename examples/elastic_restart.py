"""Fault-tolerance demo: train, get preempted (SIGTERM), restart elastically
on a DIFFERENT mesh shape from the checkpoint, keep training.

    PYTHONPATH=src python examples/elastic_restart.py
(re-executes itself with 8 fake host devices to build the two meshes)
"""
import os
import subprocess
import sys

BODY = r"""
import os, signal, tempfile, threading
import jax
from repro.configs import get_config
from repro.launch.mesh import make_mesh
from repro.launch.train import train

cfg = get_config("qwen3-0.6b").reduced()
ckpt = tempfile.mkdtemp(prefix="elastic_")

print("phase 1: mesh (4,2), SIGTERM arrives mid-run")
m1 = make_mesh((4, 2), ("data", "model"))
timer = threading.Timer(10.0, lambda: signal.raise_signal(signal.SIGTERM))
timer.start()
l1, _ = train(cfg, steps=400, batch=8, seq=64, ckpt_dir=ckpt,
              save_every=5, mesh=m1, log_every=5)
timer.cancel()
print(f"  preempted after {len(l1)} steps; checkpointed")

print("phase 2: node lost -> restart on mesh (8,1) from the checkpoint")
m2 = make_mesh((8, 1), ("data", "model"))
l2, _ = train(cfg, steps=len(l1) + 10, batch=8, seq=64, ckpt_dir=ckpt,
              save_every=100, mesh=m2, log_every=5)
assert l2[0] < l1[0] + 0.5, "must continue, not restart"
print(f"  resumed + {len(l2)} more steps on the new mesh; "
      f"loss {l1[0]:.3f} -> {l2[-1]:.3f}")
print("elastic restart OK")
"""

if __name__ == "__main__":
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env.setdefault("PYTHONPATH", os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "src"))
    r = subprocess.run([sys.executable, "-c", BODY], env=env)
    sys.exit(r.returncode)
