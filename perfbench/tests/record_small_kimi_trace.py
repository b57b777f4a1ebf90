"""Record the small trace the delta-rule and latent readers' tests read: run on
the chip, once, by hand.

    python3 perfbench/tests/record_small_kimi_trace.py chiprun_out/small_kimi_trace

A jitted function named as the pool step is (``_pool_step_paged_flash``) runs
two delta-rule state updates and one latent attention call, the program's own
kernels at a small size, six times inside a profiler session. Writes
small_kimi.xplane.pb and small_kimi.expected.json (what trace_reduce and
perfbench/moe_counts.py made of it there); copy both to perfbench/tests/data/.
"""

import glob
import json
import os
import shutil
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

import jax
import jax.numpy as jnp
import numpy as np

from perfbench import moe_counts, trace_reduce
from perfbench.kernel_time import kernel_ms_per_step
from transformer_tpu.kernels.kda_step import kda_step
from transformer_tpu.kernels.paged_latent import paged_latent_attention

out = sys.argv[1]
os.makedirs(out, exist_ok=True)
STEPS, N, H, D, W, RANK = 6, 4, 8, 128, 256, 128
table = jnp.asarray(1 + np.arange(N * 8).reshape(N, 8), jnp.int32)
lengths = jnp.asarray([40, 100, 7, 128], jnp.int32)
live = jnp.asarray([1, 1, 0, 1], jnp.int32)


def _pool_step_paged_flash(state, q, k, v, g, beta, ql, pool):
    o = jnp.zeros_like(q)
    for _ in range(2):
        step_o, state = kda_step(state, q, k, v, g, beta, live)
        o = o + step_o
    return o, state, paged_latent_attention(ql, pool, table, lengths, rank=RANK)


k = jax.random.split(jax.random.PRNGKey(0), 8)
f32 = lambda i, *s: jax.random.normal(k[i], s, jnp.float32) * 0.1  # noqa: E731
args = (f32(0, N, H, D, D), f32(1, N, H, D), f32(2, N, H, D), f32(3, N, H, D), -jnp.abs(f32(4, N, H, D)),
        jax.nn.sigmoid(f32(5, N, H)), f32(6, N, H, W).astype(jnp.bfloat16), f32(7, 1 + N * 8, 16, W).astype(jnp.bfloat16))
f = jax.jit(_pool_step_paged_flash)
jax.block_until_ready(f(*args))
d = tempfile.mkdtemp()
jax.profiler.start_trace(d)
w = jax.profiler.TraceAnnotation("perfbench.trace")
w.__enter__()
for _ in range(STEPS):
    with jax.profiler.TraceAnnotation("sched.step"):
        jax.block_until_ready(f(*args))
w.__exit__(None, None, None)
jax.profiler.stop_trace()
path = glob.glob(os.path.join(d, "plugins", "profile", "*", "*.xplane.pb"))[0]
shutil.copy(path, os.path.join(out, "small_kimi.xplane.pb"))
r = trace_reduce.reduce(trace_reduce.read_planes(path, {"sched.step", "perfbench.trace"}), {"sched.step"})
record = {"trace": r}
expected = {
    "steps": STEPS, "device": jax.devices()[0].device_kind, "bytes": os.path.getsize(path),
    "pool_steps": moe_counts.slice_pool_steps(record),
    "kda_step_s": moe_counts.kernel_seconds(record, "kda_step"),
    "kda_step_ms_per_step": kernel_ms_per_step(record, "kda_step", "_pool_step_paged_flash"),
    "paged_latent_attention_s": moe_counts.kernel_seconds(record, "paged_latent_attention"),
    "window_s": r["window_s"], "busy_s": r["busy_s"],
    "ops": r["ops"][:8], "modules": r["modules"][:3],
}
with open(os.path.join(out, "small_kimi.expected.json"), "w") as fh:
    json.dump(expected, fh, indent=1)
print(json.dumps(expected))
