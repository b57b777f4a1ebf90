"""Record the small trace the tests read: run on the chip, once, by hand.

    python3 perfbench/tests/record_small_trace.py chiprun_out/small_trace

Writes small.xplane.pb and small.expected.json (what trace_reduce made of it
there) into the given directory; copy both to perfbench/tests/data/.
"""

import glob
import json
import os
import shutil
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

import jax
import jax.numpy as jnp

from perfbench import trace_reduce

out = sys.argv[1]
os.makedirs(out, exist_ok=True)
f = jax.jit(lambda a: (a @ a).astype(jnp.bfloat16))
x = jnp.ones((2048, 2048), jnp.bfloat16)
f(x).block_until_ready()
d = tempfile.mkdtemp()
jax.profiler.start_trace(d)
w = jax.profiler.TraceAnnotation("perfbench.trace")
w.__enter__()
n = 20
for _ in range(n):
    with jax.profiler.TraceAnnotation("small.step"):
        f(x).block_until_ready()
    time.sleep(0.002)
w.__exit__(None, None, None)
jax.profiler.stop_trace()
path = glob.glob(os.path.join(d, "plugins", "profile", "*", "*.xplane.pb"))[0]
shutil.copy(path, os.path.join(out, "small.xplane.pb"))
r = trace_reduce.reduce(trace_reduce.read_planes(path, {"small.step", "perfbench.trace"}), {"small.step"})
with open(os.path.join(out, "small.expected.json"), "w") as fh:
    json.dump({"dispatches": n, "window_s": r["window_s"], "busy_s": r["busy_s"], "window_source": r["window_source"],
               "device": jax.devices()[0].device_kind, "bytes": os.path.getsize(path),
               "ops": r["ops"][:5], "modules": r["modules"][:5], "idle_by_span": r["idle_by_span"]}, fh, indent=1)
print(json.dumps({"bytes": os.path.getsize(path), "window_s": r["window_s"], "busy_s": r["busy_s"],
                  "source": r["window_source"], "lines": r["lines"], "ops": r["ops"][:5], "modules": r["modules"][:3]}))
