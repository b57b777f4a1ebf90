"""Record the small four-chip trace the all-reduce reader's test reads: run on
four chips, once, by hand.

    python3 perfbench/tests/record_small_allreduce_trace.py chiprun_out/small_allreduce_trace

One program over a data=4 mesh, six times inside a profiler session: a matmul
on each chip's shard of the rows and the all-reduce of its result (a gradient
of a replicated weight, as in data-parallel training). Writes
small_allreduce.xplane.pb and small_allreduce.expected.json (what
trace_reduce and the reader made of it there); copy both to perfbench/tests/data/.
"""

import json
import os
import shutil
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from perfbench import trace_reduce
from perfbench.run import load_reader

out = sys.argv[1]
os.makedirs(out, exist_ok=True)
STEPS = 6
mesh = Mesh(np.asarray(jax.devices()[:4]), ("data",))
rows, full = NamedSharding(mesh, P("data", None)), NamedSharding(mesh, P())


@jax.jit
def step(w, x):
    def loss(w):
        return jnp.sum(jnp.tanh(x @ w).astype(jnp.float32) ** 2)

    return jax.lax.with_sharding_constraint(w - 1e-3 * jax.grad(loss)(w), full)  # the gradient is all-reduced


k = jax.random.split(jax.random.PRNGKey(0), 2)
w = jax.device_put(jax.random.normal(k[0], (512, 512), jnp.float32) * 0.05, full)
x = jax.device_put(jax.random.normal(k[1], (1024, 512), jnp.float32), rows)
jax.block_until_ready(step(w, x))
d = tempfile.mkdtemp()
jax.profiler.start_trace(d)
span = jax.profiler.TraceAnnotation("perfbench.trace")
span.__enter__()
for _ in range(STEPS):
    w = step(w, x)
jax.block_until_ready(w)
span.__exit__(None, None, None)
jax.profiler.stop_trace()
path = trace_reduce.find_xplane(d)
shutil.copy(path, os.path.join(out, "small_allreduce.xplane.pb"))
reduced = trace_reduce.reduce(trace_reduce.read_planes(path, {"perfbench.trace"}), set())
if reduced is None:
    sys.exit("the trace holds no device plane: run this on the chips")
share = load_reader("layer_metrics", "comm.allreduce_share")({"train": {"steps": STEPS}, "trace": reduced})
expected = {"steps": STEPS, "device": jax.devices()[0].device_kind, "devices": reduced["devices"],
            "bytes": os.path.getsize(path), "allreduce_share": share, "busy_s": reduced["busy_s"],
            "window_s": reduced["window_s"], "ops": reduced["ops"][:12]}
with open(os.path.join(out, "small_allreduce.expected.json"), "w") as f:
    json.dump(expected, f, indent=1)
print(json.dumps({k: v for k, v in expected.items() if k != "ops"}))
for name, seconds, count in reduced["ops"][:12]:
    print(f"{seconds:.6f} x{count} {name[:160]}")
