"""Record the small trace the expert and band readers' tests read: run on the
chip, once, by hand.

    python3 perfbench/tests/record_small_moe_trace.py chiprun_out/small_moe_trace

A jitted function named as the pool step is (``_pool_step_paged_flash``) runs
two grouped expert products and three paged attention calls, the program's
own kernels at a small size, six times inside a profiler session. Writes
small_moe.xplane.pb and small_moe.expected.json (what trace_reduce and
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
from transformer_tpu.kernels.moe_ffn import moe_expert_ffn
from transformer_tpu.kernels.paged_flash import paged_flash_attention

out = sys.argv[1]
os.makedirs(out, exist_ok=True)
STEPS, TILE, D, F, E = 6, 16, 256, 256, 4
group = jnp.asarray([0, 0, 2, 3], jnp.int32)
live = jnp.asarray(3, jnp.int32)
table = jnp.asarray(1 + np.arange(2 * 8).reshape(2, 8), jnp.int32)
lengths = jnp.asarray([40, 100], jnp.int32)


def _pool_step_paged_flash(x, wg, wi, wo, q, kp, vp):
    for _ in range(2):
        x = x + moe_expert_ffn(x, wg, wi, wo, group, live, tile_rows=TILE, block_dff=128)[: x.shape[0]]
    for window in (0, 32, 32):
        q = q + paged_flash_attention(q, kp, vp, table, lengths, window=window)
    return x, q


k = jax.random.split(jax.random.PRNGKey(0), 7)
bf = lambda i, *s: jax.random.normal(k[i], s, jnp.bfloat16) * 0.1  # noqa: E731
args = (bf(0, 4 * TILE, D), bf(1, E, D, F), bf(2, E, D, F), bf(3, E, F, D), bf(4, 2, 1, 8, 128),
        bf(5, 17, 16, 8, 128), bf(6, 17, 16, 8, 128))
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
shutil.copy(path, os.path.join(out, "small_moe.xplane.pb"))
r = trace_reduce.reduce(trace_reduce.read_planes(path, {"sched.step", "perfbench.trace"}), {"sched.step"})
record = {"trace": r}
expected = {
    "steps": STEPS, "device": jax.devices()[0].device_kind, "bytes": os.path.getsize(path),
    "pool_steps": moe_counts.slice_pool_steps(record),
    "moe_expert_ffn_s": moe_counts.kernel_seconds(record, "moe_expert_ffn"),
    "paged_flash_attention_s": moe_counts.kernel_seconds(record, "paged_flash_attention"),
    "window_s": r["window_s"], "busy_s": r["busy_s"],
    "ops": r["ops"][:8], "modules": r["modules"][:3],
}
with open(os.path.join(out, "small_moe.expected.json"), "w") as fh:
    json.dump(expected, fh, indent=1)
print(json.dumps(expected))
