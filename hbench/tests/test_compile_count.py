"""A program first needed inside the window counts as a window compile
also where the persistent compilation cache already holds it."""
import json
import subprocess
import sys

from hbench.tests import tiny

SCRIPT = """
import json, pathlib, sys
sys.path[:0] = [sys.argv[3]]
import jax, jax.numpy as jnp
from hbench import bench
bench.enable_compile_cache(pathlib.Path(sys.argv[1]))
count = bench.CompileCount()
hits = []
jax.monitoring.register_event_listener(
    lambda e, **_: hits.append(e) if e.endswith("/cache_hits") else None)
jax.jit(lambda x: x * 3 + 1)(jnp.ones(int(sys.argv[2]))).block_until_ready()
print(json.dumps({"n": count.n, "hits": len(hits)}))
"""


def _run(root, n):
    p = subprocess.run([sys.executable, "-c", SCRIPT, str(root), str(n),
                        str(tiny.ROOT)], capture_output=True, text=True,
                       timeout=120, env={"JAX_PLATFORMS": "cpu",
                                         "PATH": "/usr/bin:/bin"})
    assert p.returncode == 0, p.stderr
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_cache_hit_counts_as_a_compile(tmp_path):
    cold = _run(tmp_path, 7)
    assert cold["n"] >= 1 and cold["hits"] == 0
    # A new process finds every program in the cache, and the count
    # still sees each of them.
    assert _run(tmp_path, 7) == {"n": cold["n"], "hits": cold["n"]}
    new_shape = _run(tmp_path, 9)
    assert new_shape["n"] > new_shape["hits"]
