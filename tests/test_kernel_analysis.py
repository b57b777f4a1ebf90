"""TPA300 kernel-verifier tests: hand-computed VMEM, per-rule twins, the
seeded corpora, CLI exit codes + baseline workflow, the costs cross-check,
and the package-wide zero-findings pin. Slow canaries prove the verifier
actually DETECTS the three bug classes it exists for."""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from transformer_tpu.analysis.costs import pallas_call_flops
from transformer_tpu.analysis.kernels import (
    DEFAULT_GENERATION,
    VMEM_BUDGETS,
    analyze_entries,
    compare_kernels_to_baseline,
    default_kernels_baseline_path,
    run_kernels,
    write_kernels_baseline,
)

FIXTURES = os.path.join(os.path.dirname(__file__), "fixtures")
BAD = os.path.join(FIXTURES, "tpa_kernel_bad_corpus.py")
GOOD = os.path.join(FIXTURES, "tpa_kernel_good_corpus.py")

_ARB = pltpu.CompilerParams(dimension_semantics=("arbitrary",))


def _copy_entry(block_q=8, out_map=None):
    """grid (2,): x (16,128) f32 in blocks of (block_q,128); out either
    grid-varying (default) or pinned to block 0."""

    def kern(x_ref, o_ref):
        o_ref[...] = x_ref[...] * 2.0

    def factory():
        def fn(x):
            return pl.pallas_call(
                kern,
                grid=(2,),
                in_specs=[pl.BlockSpec((block_q, 128), lambda i: (i, 0))],
                out_specs=pl.BlockSpec(
                    (block_q, 128), out_map or (lambda i: (i, 0))
                ),
                out_shape=jax.ShapeDtypeStruct((2 * block_q, 128), jnp.float32),
                compiler_params=_ARB,
                interpret=True,
            )(x)

        return fn, (jax.ShapeDtypeStruct((2 * block_q, 128), jnp.float32),)

    return factory


class TestVmemModel:
    def test_hand_computed_double_buffered(self):
        """Both specs vary over the grid -> 2x block bytes each, no scratch:
        2 * (8*128*4) + 2 * (8*128*4) = 16384."""
        res = analyze_entries({"copy": _copy_entry()}, ast_targets=[])
        assert not res.violations and not res.findings
        (r,) = res.reports
        assert r.predicted_vmem_bytes == 16384
        assert r.vmem_breakdown == {"in[0]": 8192, "out[0]": 8192}
        assert r.grid == (2,) and r.checked_points == 2 and not r.sampled

    def test_hand_computed_with_scratch_and_invariant_out(self):
        """In spec varies (2x), out pinned to one block (1x), fp32 scratch
        counted once: 2*4096 + 4096 + 4096 = 16384."""

        def kern(x_ref, o_ref, acc_ref):
            @pl.when(pl.program_id(0) == 0)
            def _init():
                acc_ref[...] = jnp.zeros_like(acc_ref)

            acc_ref[...] += x_ref[...]

            @pl.when(pl.program_id(0) == pl.num_programs(0) - 1)
            def _fin():
                o_ref[...] = acc_ref[...]

        def factory():
            def fn(x):
                return pl.pallas_call(
                    kern,
                    grid=(2,),
                    in_specs=[pl.BlockSpec((8, 128), lambda i: (i, 0))],
                    out_specs=pl.BlockSpec((8, 128), lambda i: (0, 0)),
                    out_shape=jax.ShapeDtypeStruct((8, 128), jnp.float32),
                    scratch_shapes=[pltpu.VMEM((8, 128), jnp.float32)],
                    compiler_params=_ARB,
                    interpret=True,
                )(x)

            return fn, (jax.ShapeDtypeStruct((16, 128), jnp.float32),)

        res = analyze_entries({"acc": factory}, ast_targets=[])
        assert not res.violations and not res.findings, (
            res.violations,
            res.findings,
        )
        (r,) = res.reports
        assert r.vmem_breakdown == {
            "in[0]": 8192,
            "out[0]": 4096,
            "scratch[0]": 4096,
        }
        assert r.predicted_vmem_bytes == 16384

    def test_budget_table_generations(self):
        assert VMEM_BUDGETS[DEFAULT_GENERATION] == 16 * 1024 * 1024
        assert VMEM_BUDGETS["v6e"] == 32 * 1024 * 1024


class TestRuleTwins:
    """Inline bad/good pairs: each rule fires on the bad twin and stays
    silent on the good one (the full per-rule matrix rides the corpora)."""

    def _codes(self, factory):
        res = analyze_entries({"t": factory}, ast_targets=[])
        assert not res.violations, res.violations
        return sorted({f.code for f in res.findings})

    def test_tpa301_bf16_accumulator(self):
        def kern_bad(x_ref, o_ref, acc_ref):
            @pl.when(pl.program_id(0) == 0)
            def _i():
                acc_ref[...] = jnp.zeros_like(acc_ref)

            acc_ref[...] += x_ref[...].astype(jnp.bfloat16)

            @pl.when(pl.program_id(0) == pl.num_programs(0) - 1)
            def _f():
                o_ref[...] = acc_ref[...].astype(jnp.float32)

        def make(dtype, kern):
            def factory():
                def fn(x):
                    return pl.pallas_call(
                        kern,
                        grid=(2,),
                        in_specs=[pl.BlockSpec((8, 128), lambda i: (i, 0))],
                        out_specs=pl.BlockSpec((8, 128), lambda i: (0, 0)),
                        out_shape=jax.ShapeDtypeStruct((8, 128), jnp.float32),
                        scratch_shapes=[pltpu.VMEM((8, 128), dtype)],
                        compiler_params=_ARB,
                        interpret=True,
                    )(x)

                return fn, (jax.ShapeDtypeStruct((16, 128), jnp.float32),)

            return factory

        def kern_good(x_ref, o_ref, acc_ref):
            @pl.when(pl.program_id(0) == 0)
            def _i():
                acc_ref[...] = jnp.zeros_like(acc_ref)

            acc_ref[...] += x_ref[...]

            @pl.when(pl.program_id(0) == pl.num_programs(0) - 1)
            def _f():
                o_ref[...] = acc_ref[...]

        assert self._codes(make(jnp.bfloat16, kern_bad)) == ["TPA301"]
        assert self._codes(make(jnp.float32, kern_good)) == []

    def test_tpa303_masked_exp(self):
        def kern_bad(x_ref, o_ref):
            s = jnp.where(x_ref[...] > 0, x_ref[...], -1e30)
            o_ref[...] = jnp.exp(s)

        def kern_good(x_ref, o_ref):
            s = jnp.where(x_ref[...] > 0, x_ref[...], -1e30)
            o_ref[...] = jnp.where(s > -1e29, jnp.exp(s), 0.0)

        def make(kern):
            def factory():
                def fn(x):
                    return pl.pallas_call(
                        kern,
                        grid=(1,),
                        in_specs=[pl.BlockSpec((8, 128), lambda i: (0, 0))],
                        out_specs=pl.BlockSpec((8, 128), lambda i: (0, 0)),
                        out_shape=jax.ShapeDtypeStruct((8, 128), jnp.float32),
                        interpret=True,
                    )(x)

                return fn, (jax.ShapeDtypeStruct((8, 128), jnp.float32),)

            return factory

        assert self._codes(make(kern_bad)) == ["TPA303"]
        assert self._codes(make(kern_good)) == []

    def test_out_race_detected(self):
        """Out block pinned to (0,0) while the grid has 2 steps, writes
        unguarded, and the revisited axis is declared 'parallel' — both
        the semantics and the write-discipline violations fire."""

        def kern(x_ref, o_ref):
            o_ref[...] = x_ref[...]

        def factory():
            def fn(x):
                return pl.pallas_call(
                    kern,
                    grid=(2,),
                    in_specs=[pl.BlockSpec((8, 128), lambda i: (i, 0))],
                    out_specs=pl.BlockSpec((8, 128), lambda i: (0, 0)),
                    out_shape=jax.ShapeDtypeStruct((8, 128), jnp.float32),
                    compiler_params=pltpu.CompilerParams(
                        dimension_semantics=("parallel",)
                    ),
                    interpret=True,
                )(x)

            return fn, (jax.ShapeDtypeStruct((16, 128), jnp.float32),)

        res = analyze_entries({"race": factory}, ast_targets=[])
        assert any("write race" in v for v in res.violations), res.violations
        assert any("unconditionally" in v for v in res.violations), res.violations


class TestCorpora:
    def test_bad_corpus_fires_every_rule(self):
        res = run_kernels(paths=[BAD], compare=False)
        codes = {f.code for f in res.findings}
        assert codes == {"TPA300", "TPA301", "TPA302", "TPA303", "TPA304",
                         "TPA305"}, codes
        assert not res.violations, res.violations

    def test_good_corpus_clean(self):
        res = run_kernels(paths=[GOOD], compare=False)
        assert not res.findings and not res.violations, (
            res.findings,
            res.violations,
        )
        assert res.ok and len(res.reports) == 5

    def test_baseline_roundtrip_in_process(self, tmp_path):
        base = str(tmp_path / "kb.json")
        res = run_kernels(paths=[BAD], compare=False)
        write_kernels_baseline(res, base)
        res2 = run_kernels(paths=[BAD], baseline_path=base)
        assert res2.ok, (res2.findings, res2.violations, res2.regressions)
        assert res2.baselined == len(res.findings) > 0

    def test_vmem_growth_is_a_regression(self, tmp_path):
        base = str(tmp_path / "kb.json")
        small = analyze_entries({"copy": _copy_entry(block_q=8)}, ast_targets=[])
        write_kernels_baseline(small, base)
        big = analyze_entries({"copy": _copy_entry(block_q=16)}, ast_targets=[])
        big = compare_kernels_to_baseline(big, base)
        assert any("predicted_vmem_bytes grew" in g for g in big.regressions), (
            big.regressions
        )
        # Shrinkage is a note, not a failure.
        small2 = analyze_entries(
            {"copy": _copy_entry(block_q=8)}, ast_targets=[]
        )
        write_kernels_baseline(
            analyze_entries({"copy": _copy_entry(block_q=16)}, ast_targets=[]),
            base,
        )
        small2 = compare_kernels_to_baseline(small2, base)
        assert small2.ok and any("improved" in n for n in small2.notes)

    def test_coverage_loss_is_a_regression(self, tmp_path):
        base = str(tmp_path / "kb.json")
        both = analyze_entries(
            {"a": _copy_entry(8), "b": _copy_entry(16)}, ast_targets=[]
        )
        write_kernels_baseline(both, base)
        one = analyze_entries({"a": _copy_entry(8)}, ast_targets=[])
        one = compare_kernels_to_baseline(one, base)
        assert any("coverage lost" in g for g in one.regressions), one.regressions


class TestCli:
    def _run(self, *argv):
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        return subprocess.run(
            [sys.executable, "-m", "transformer_tpu.analysis", "kernels", *argv],
            capture_output=True,
            text=True,
            timeout=560,
            env=env,
        )

    def test_exit_codes_and_baseline_workflow(self, tmp_path):
        base = str(tmp_path / "kb.json")
        # bad corpus, no baseline -> findings -> exit 1
        p = self._run("--paths", BAD, "--baseline", base)
        assert p.returncode == 1, p.stdout + p.stderr
        # bank it -> exit 0
        p = self._run("--paths", BAD, "--baseline", base, "--update-baseline")
        assert p.returncode == 0, p.stdout + p.stderr
        # rerun against the bank -> clean exit 0, json parses
        p = self._run("--paths", BAD, "--baseline", base, "--format", "json")
        assert p.returncode == 0, p.stdout + p.stderr
        doc = json.loads(p.stdout)
        assert doc["ok"] is True and doc["baselined"] > 0
        # good corpus needs no baseline at all
        p = self._run("--paths", GOOD)
        assert p.returncode == 0, p.stdout + p.stderr


class TestCostsCrossCheck:
    """Satellite: the verifier's per-kernel FLOPs and costs' _walk_eqns_hbm
    pricing share ONE extraction helper — divergence is a hard failure."""

    def _dot_program(self):
        def kern(x_ref, w_ref, o_ref):
            o_ref[...] = jnp.dot(
                x_ref[...], w_ref[...], preferred_element_type=jnp.float32
            )

        def fn(x, w):
            return pl.pallas_call(
                kern,
                grid=(2,),
                in_specs=[
                    pl.BlockSpec((8, 8), lambda i: (i, 0)),
                    pl.BlockSpec((8, 8), lambda i: (0, 0)),
                ],
                out_specs=pl.BlockSpec((8, 8), lambda i: (i, 0)),
                out_shape=jax.ShapeDtypeStruct((16, 8), jnp.float32),
                interpret=True,
            )(x, w)

        return fn, (
            jax.ShapeDtypeStruct((16, 8), jnp.float32),
            jax.ShapeDtypeStruct((8, 8), jnp.float32),
        )

    def test_hand_computed_dot_flops(self):
        """(8,8)@(8,8) dot = 2*8*8*8 = 1024 flops/step x 2 grid steps."""
        fn, args = self._dot_program()
        closed = jax.make_jaxpr(fn)(*args)
        from transformer_tpu.analysis.kernels import _iter_pallas_eqns

        (eqn,) = list(_iter_pallas_eqns(closed.jaxpr))
        assert pallas_call_flops(eqn) == 2048

    def test_walk_and_helper_agree(self):
        """Total flops from costs' walk == outside-kernel flops + the shared
        helper summed over every pallas_call eqn (no double counting, no
        drift)."""
        from transformer_tpu.analysis.costs import _eqn_flops, _walk_eqns_hbm

        fn, args = self._dot_program()
        closed = jax.make_jaxpr(lambda x, w: fn(x, w) + x)(*args)
        total = 0
        outside = 0
        kernel_sum = 0
        for eqn, w, in_kernel in _walk_eqns_hbm(closed.jaxpr):
            total += w * _eqn_flops(eqn)
            if not in_kernel:
                outside += w * _eqn_flops(eqn)
                if eqn.primitive.name == "pallas_call":
                    kernel_sum += pallas_call_flops(eqn, 1)
        assert kernel_sum == 2048
        assert total == outside + kernel_sum

    def test_package_reports_priced_by_shared_helper(self):
        """Every banked flops_per_call in the shipped baseline must be
        reproduced by the live verifier (compare_kernels_to_baseline notes
        any drift; a clean package run means zero drift notes)."""
        res = run_kernels()
        assert res.ok, (res.findings, res.violations, res.regressions)
        assert not any("drifted" in n for n in res.notes), res.notes
        assert all(
            r.flops_per_call > 0
            for r in res.reports
            if r.kernel in ("_fwd_kernel", "_paged_kernel", "_fused_kernel")
        )


class TestPackagePin:
    def test_package_zero_unbaselined(self):
        """THE pin: the shipped package verifies clean against its checked-in
        baseline — every shipped kernel enumerated, in-bounds over its full
        grid, VMEM banked and under budget."""
        res = run_kernels()
        assert res.ok, (res.findings, res.violations, res.regressions)
        kernels = {r.kernel for r in res.reports}
        assert {
            "_fwd_kernel",
            "_dq_kernel",
            "_dkdv_kernel",
            "_ring_step_kernel",
            "_paged_kernel",
            "_fused_kernel",
        } <= kernels, kernels
        assert all(not r.sampled for r in res.reports)
        assert all(r.fits_budget for r in res.reports)
        assert os.path.exists(default_kernels_baseline_path())

    def test_gqa_variants_enumerated(self):
        res = run_kernels()
        entries = {r.entry for r in res.reports}
        assert "flash.grad[gqa,fp32]" in entries
        assert "paged_flash[gqa,verify]" in entries
        assert any(e.startswith("serve.pool_step_paged_flash") for e in entries)


@pytest.mark.slow
class TestCanaries:
    """Detection proof: each canary is the bug class the verifier exists
    for, planted deliberately and required to be flagged."""

    def test_out_of_bounds_index_map(self):
        def kern(x_ref, o_ref):
            o_ref[...] = x_ref[...]

        def factory():
            def fn(x):
                return pl.pallas_call(
                    kern,
                    grid=(2,),
                    in_specs=[pl.BlockSpec((8, 128), lambda i: (i + 1, 0))],
                    out_specs=pl.BlockSpec((8, 128), lambda i: (i, 0)),
                    out_shape=jax.ShapeDtypeStruct((16, 128), jnp.float32),
                    interpret=True,
                )(x)

            return fn, (jax.ShapeDtypeStruct((16, 128), jnp.float32),)

        res = analyze_entries({"oob": factory}, ast_targets=[])
        assert any("out of bounds" in v for v in res.violations), res.violations

    def test_vmem_blowup(self):
        def kern(x_ref, o_ref):
            o_ref[...] = x_ref[...]

        def make(rows):
            def factory():
                def fn(x):
                    return pl.pallas_call(
                        kern,
                        grid=(2,),
                        in_specs=[pl.BlockSpec((rows, 1024), lambda i: (i, 0))],
                        out_specs=pl.BlockSpec((rows, 1024), lambda i: (i, 0)),
                        out_shape=jax.ShapeDtypeStruct(
                            (2 * rows, 1024), jnp.float32
                        ),
                        interpret=True,
                    )(x)

                return fn, (
                    jax.ShapeDtypeStruct((2 * rows, 1024), jnp.float32),
                )

            return factory

        # 4096-row f32 blocks, double-buffered in+out = 64 MiB: over any budget.
        res = analyze_entries({"vmem": make(4096)}, ast_targets=[])
        assert any("exceeds v5e budget" in v for v in res.violations), (
            res.violations
        )
        # 20 MiB case: over v5e's 16 MiB, absorbed by v6e's 32 MiB — the
        # budget table is live, not a single constant.
        mid = make(1280)
        res5 = analyze_entries({"vmem": mid}, ast_targets=[])
        assert any("exceeds v5e budget" in v for v in res5.violations)
        res6 = analyze_entries({"vmem": mid}, generation="v6e", ast_targets=[])
        assert not res6.violations, res6.violations

    def test_bf16_accumulator(self):
        res = run_kernels(paths=[BAD], compare=False)
        tpa301 = [f for f in res.findings if f.code == "TPA301"]
        assert tpa301 and tpa301[0].symbol == "_acc_bf16_kernel"
