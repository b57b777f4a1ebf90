"""Sharded replicas: one replica = one multi-device pjit program.

``--mesh N`` (or ``data=N``) turns a replica's scheduler into a pjit
program over an N-device serving mesh built by ``parallel/mesh.py``:

- **Params replicate.** ``parallel/sharding.py:state_shardings`` applies
  the partition rules over the serving mesh — whose fsdp/model/expert
  axes are size 1, so every rule resolves to effective replication. This
  is a deliberate layout, not a shortcut: replicated params mean every
  slot's forward is device-local, which is what keeps the decode step
  free of collectives (the Densifying argument: keep the collective set
  small and dense — here, empty) and greedy/sampled answers bit-identical
  across mesh sizes (splitting a float reduction across devices is what
  breaks parity; pure data movement cannot).
- **The KV pool shards on its leading storage axis** — the slot axis for
  the dense layout, the block-row axis for the paged pool — via one
  pytree-prefix ``NamedSharding``. The host-side block-table allocator
  (``kernels/kv_pool.py``) is untouched: tables and indices stay
  replicated host-authoritative arrays, so prefix aliasing, CoW splits,
  spill/restore, and the ``--disaggregate`` wire format work shard-wise
  for free. Cross-shard block traffic (a slot's table row may reference
  blocks resident on any shard) is GSPMD-inserted deterministic data
  movement, bit-exact by construction.
- **The canned jitted programs get explicit in/out shardings** — the
  ``ShardedPrograms`` factory below builds per-scheduler jit twins of the
  module-level programs in ``serve/scheduler.py`` from their unwrapped
  functions, with identical signatures and static/donation structure, so
  every scheduler call site dispatches the twin unchanged. Donated pool
  args carry equal in/out shardings (TPA203's contract), and all call
  sites already pass static args positionally (pjit refuses kwargs once
  in_shardings is given).

This module imports jax lazily so ``serve/replica.py`` can parse
``--mesh`` and grow the virtual CPU platform (``XLA_FLAGS=
--xla_force_host_platform_device_count=N``) BEFORE the first jax import
— the same trick tests/conftest.py and ``analysis/__main__.py`` use.
"""

from __future__ import annotations

# Dense decode/verify at any mesh size must stay collective-free; the
# compiled-HLO gate in analysis/sharding.py (serving_hlo_collectives)
# pins that claim against these exact twins.
_HOT_AXES = ("data", "fsdp", "expert")


def parse_mesh_spec(spec: "str | int | None") -> "int | None":
    """``--mesh`` flag -> serving mesh size. Accepts '' / None (unsharded),
    'N', or 'data=N' (the canonical form heartbeats report). Loud on
    anything else — a silently-misparsed mesh flag would bootstrap a
    replica at the wrong shape, exactly what the supervisor refuses."""
    if spec is None:
        return None
    if isinstance(spec, int):
        n = spec
    else:
        s = str(spec).strip()
        if not s:
            return None
        if s.startswith("data="):
            s = s[len("data="):]
        try:
            n = int(s)
        except ValueError:
            raise ValueError(
                f"--mesh must be '', 'N', or 'data=N', got {spec!r}"
            ) from None
    if n < 1:
        raise ValueError(f"--mesh size must be >= 1, got {n}")
    return n


def normalize_mesh_spec(spec: "str | int | None") -> "str | None":
    """Canonical mesh-shape string ('data=N') — the ONE rendering the
    replica's ready/heartbeat messages report and the supervisor's
    ``expected_mesh`` compares against, so flag spellings ('2' vs
    'data=2') can never alias into a false mismatch."""
    n = parse_mesh_spec(spec)
    return None if n is None else f"data={n}"


def serving_mesh(n: int):
    """The N-device serving mesh: ``MeshConfig(data=N)`` over the first N
    local devices. All other axes are size 1, so the partition rules
    resolve to replication and the batch axes ('data', 'fsdp', 'expert')
    collapse onto 'data' — see the module docstring for why."""
    import jax

    from transformer_tpu.config import MeshConfig
    from transformer_tpu.parallel.mesh import make_mesh

    devices = jax.devices()
    if len(devices) < n:
        raise ValueError(
            f"mesh data={n} needs {n} devices, platform has {len(devices)} "
            f"({devices[0].platform}). On CPU, grow the virtual platform "
            "with XLA_FLAGS=--xla_force_host_platform_device_count="
            f"{n} before jax initializes (serve/replica.py --mesh does "
            "this automatically in its own process)."
        )
    return make_mesh(MeshConfig(data=n), devices[:n])


class ShardedPrograms:
    """jit twins of the scheduler's canned programs with explicit in/out
    shardings over a serving mesh. Attribute names mirror the module
    programs minus the leading underscore; signatures, static argnames,
    and donation structure are identical, so ``ContinuousScheduler``
    swaps them in via its ``_fn_*`` dispatch with zero call-site churn.
    """

    def __init__(self, mesh, params):
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        from transformer_tpu.parallel.sharding import state_shardings
        from transformer_tpu.serve import scheduler as smod

        self.mesh = mesh
        axes = tuple(a for a in _HOT_AXES if a in mesh.shape)
        # One pytree-prefix sharding for the whole pool: every leaf of
        # both KV layouts carries the sharded storage axis LEADING (dense:
        # stacked slots + the (N,) index; paged: block-pool rows), which
        # is what lets one prefix cover k/v/scale leaves of every cache
        # variant (bf16/int8/GQA) without per-leaf rules.
        self.pool = NamedSharding(mesh, P(axes))
        self.repl = NamedSharding(mesh, P())
        self.params = state_shardings(params, mesh)
        PS, L, R = self.params, self.pool, self.repl

        def twin(fn, *, statics=(), donate=(), ins, outs):
            return jax.jit(
                fn.__wrapped__ if hasattr(fn, "__wrapped__") else fn,
                static_argnames=statics, donate_argnums=donate,
                in_shardings=ins, out_shardings=outs,
            )

        # ---- dense layout -------------------------------------------------
        self.pool_step = twin(
            smod._pool_step, statics=("cfg",), donate=(1,),
            ins=(PS, L, L), outs=(L, L),
        )
        self.pool_verify = twin(
            smod._pool_verify, statics=("cfg",), donate=(1,),
            ins=(PS, L, L), outs=(L, L),
        )
        self.pool_rollback = twin(
            smod._pool_rollback, donate=(0,), ins=(L, L), outs=L,
        )
        self.slot_prefill = twin(
            smod._slot_prefill, statics=("cfg", "chunk"),
            ins=(PS, L, R, R, R), outs=(R, L),
        )
        self.slot_restore = twin(
            smod._slot_restore, ins=(L, R, R), outs=L,
        )
        self.slot_read_blocks = twin(
            smod._slot_read_blocks, statics=("n",), ins=(L, R, R), outs=R,
        )
        # ---- paged layout -------------------------------------------------
        # Tables/indices stay replicated (host-authoritative, a few KB);
        # the pool's block rows shard. paged_flash has no twin: the fused
        # Pallas kernels are single-device programs by construction, and
        # the scheduler refuses that combination at build time.
        self.pool_step_paged = twin(
            smod._pool_step_paged,
            statics=("cfg", "block_tokens", "buf_len"), donate=(1,),
            ins=(PS, L, R, R, R), outs=(R, L),
        )
        self.pool_verify_paged = twin(
            smod._pool_verify_paged,
            statics=("cfg", "block_tokens", "buf_len"), donate=(1,),
            ins=(PS, L, R, R, R), outs=(R, L),
        )
        self.slot_prefill_paged = twin(
            smod._slot_prefill_paged,
            statics=("cfg", "chunk", "block_tokens", "buf_len"), donate=(1,),
            ins=(PS, L, R, R, R, R), outs=(R, L),
        )
        self.pool_write_blocks = twin(
            smod._pool_write_blocks, ins=(L, R, R), outs=L,
        )
        self.pool_read_block = twin(
            smod._pool_read_block, statics=("head_dim",), ins=(L, R), outs=R,
        )
        self.pool_copy_blocks = twin(
            smod._pool_copy_blocks, ins=(L, R, R), outs=L,
        )

    def place_params(self, params):
        """Commit a param pytree to its partition-rule shardings (no-op
        bytes-wise on the serving mesh — the rules replicate — but the
        commitment is what makes every later dispatch resharding-free)."""
        import jax

        return jax.device_put(params, self.params)

    def place_pool(self, caches):
        """Commit pool KV storage to the leading-axis shard."""
        import jax

        return jax.device_put(caches, self.pool)

    def check_staged_shardings(self, staged) -> list:
        """The staged-params twin check grown to sharding specs: leaves
        already committed to a device layout must agree with the serving
        mesh's partition rules — a staged pytree living on a DIFFERENT
        mesh (wrong device set or wrong spec) would make the swap reshard
        or crash mid-flight. Host arrays (the checkpoint-load case) pass:
        ``place_params`` commits them. Returns human-readable mismatch
        strings, empty when clean."""
        import jax

        flat_want = jax.tree_util.tree_flatten_with_path(self.params)[0]
        flat_got = jax.tree_util.tree_flatten_with_path(staged)[0]
        bad = []
        for (path, want), (_, leaf) in zip(flat_want, flat_got):
            got = getattr(leaf, "sharding", None)
            if got is None or not isinstance(leaf, jax.Array):
                continue  # host array: placed at stage time
            if getattr(leaf, "committed", True) and not got.is_equivalent_to(
                want, getattr(leaf, "ndim", 0)
            ):
                key = "/".join(str(getattr(p, "key", p)) for p in path)
                bad.append(f"{key}: staged on {got} != serving {want}")
        return bad
