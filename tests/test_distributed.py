"""Distributed-path integration tests (8 fake CPU devices via subprocess, so
the main pytest process keeps its single real device)."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_fake_devices(code: str, n: int = 8) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n}"
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, env=env, timeout=600)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr[-3000:]}"
    return r.stdout


class TestShardedTraining:
    def test_fsdp_tp_matches_single_device(self):
        """The same train step under fsdp+tp sharding on a 4x2 mesh produces
        the single-device loss (placement never changes values — the SPMD
        version of the scheduler-invariance property)."""
        out = run_fake_devices("""
            import jax, jax.numpy as jnp, numpy as np
            from repro.configs import get_config
            from repro.launch.mesh import make_host_mesh
            from repro.sharding.plans import Plan, activation_rules, param_sharding_tree
            from repro.train import AdamConfig, init_train_state, make_train_step

            cfg = get_config('gemma3-4b').reduced()
            opt = AdamConfig(lr=1e-2, warmup_steps=2, total_steps=20)
            state = init_train_state(cfg, jax.random.PRNGKey(0))
            rng = np.random.default_rng(0)
            batch = {
                'tokens': jnp.asarray(rng.integers(0, cfg.vocab, (8, 16)), jnp.int32),
                'labels': jnp.asarray(rng.integers(0, cfg.vocab, (8, 16)), jnp.int32),
            }

            # single-device baseline
            plan0 = Plan('local', batch_axes=(), tp_axis=None, remat='dots')
            s0, m0 = jax.jit(make_train_step(cfg, plan0, opt))(state, batch)

            # sharded: 4-way data x 2-way model
            mesh = make_host_mesh(model_axis=2)
            plan = Plan('fsdp_tp', batch_axes=('data',), tp_axis='model',
                        fsdp_axis=('data',), remat='dots')
            rules = activation_rules(plan, mesh, cfg)
            psh = param_sharding_tree(cfg, plan, mesh)
            from jax.sharding import NamedSharding, PartitionSpec as P
            state_sh = {'params': psh,
                        'opt': {'m': psh, 'v': psh,
                                'step': NamedSharding(mesh, P())}}
            batch_sh = {k: NamedSharding(mesh, P('data', None)) for k in batch}
            state1 = jax.device_put(init_train_state(cfg, jax.random.PRNGKey(0)), state_sh)
            batch1 = jax.device_put(batch, batch_sh)
            step = jax.jit(make_train_step(cfg, plan, opt, rules),
                           in_shardings=(state_sh, batch_sh),
                           out_shardings=(state_sh, None))
            with mesh:
                s1, m1 = step(state1, batch1)
            d = abs(float(m0['loss']) - float(m1['loss']))
            print('LOSS_DELTA', d)
            assert d < 5e-3, d
            # params agree after one update
            w0 = np.asarray(s0['params']['embed'], np.float32)
            w1 = np.asarray(jax.device_get(s1['params']['embed']), np.float32)
            print('PARAM_DELTA', float(np.abs(w0 - w1).max()))
            assert np.allclose(w0, w1, atol=5e-2)
        """)
        assert "LOSS_DELTA" in out

    def test_moe_ep_training_runs_sharded(self):
        out = run_fake_devices("""
            import jax, jax.numpy as jnp, numpy as np
            from repro.configs import get_config
            from repro.launch.mesh import make_host_mesh
            from repro.sharding.plans import Plan, activation_rules, param_sharding_tree
            from repro.train import AdamConfig, init_train_state, make_train_step
            from jax.sharding import NamedSharding, PartitionSpec as P

            cfg = get_config('phi3.5-moe-42b-a6.6b').reduced()
            mesh = make_host_mesh(model_axis=4)
            plan = Plan('ep', batch_axes=('data',), tp_axis='model', ep=True,
                        remat='dots')
            rules = activation_rules(plan, mesh, cfg)
            psh = param_sharding_tree(cfg, plan, mesh)
            state_sh = {'params': psh, 'opt': {'m': psh, 'v': psh,
                        'step': NamedSharding(mesh, P())}}
            state = jax.device_put(
                init_train_state(cfg, jax.random.PRNGKey(0)), state_sh)
            rng = np.random.default_rng(0)
            batch = {
                'tokens': jnp.asarray(rng.integers(0, cfg.vocab, (8, 16)), jnp.int32),
                'labels': jnp.asarray(rng.integers(0, cfg.vocab, (8, 16)), jnp.int32),
            }
            step = jax.jit(make_train_step(cfg, plan, AdamConfig(), rules),
                           in_shardings=(state_sh, None), out_shardings=(state_sh, None))
            with mesh:
                state, metrics = step(state, batch)
            loss = float(metrics['loss'])
            print('MOE_LOSS', loss)
            assert np.isfinite(loss)
        """)
        assert "MOE_LOSS" in out

    def test_dryrun_cell_on_host_mesh(self):
        """A miniature of the production dry-run: lower+compile a serve_step
        with sharded cache on a 4x2 mesh and parse nonzero collectives."""
        out = run_fake_devices("""
            import jax, jax.numpy as jnp
            from repro.configs import get_config
            from repro.launch.mesh import make_host_mesh
            from repro.launch.shapes import cache_struct
            from repro.models import param_struct
            from repro.sharding.hlo import collective_bytes
            from repro.sharding.plans import Plan, activation_rules
            from repro.train import make_serve_step
            from jax.sharding import NamedSharding, PartitionSpec as P

            cfg = get_config('hymba-1.5b').reduced()
            mesh = make_host_mesh(model_axis=2)
            plan = Plan('serve', batch_axes=('data',), tp_axis='model', remat='none')
            rules = activation_rules(plan, mesh, cfg)
            params = param_struct(cfg)
            cache = cache_struct(cfg, 8, 64)
            tokens = jax.ShapeDtypeStruct((8, 1), jnp.int32)
            fn = make_serve_step(cfg, plan, rules)
            with mesh:
                lowered = jax.jit(fn).lower(params, tokens, cache)
                compiled = lowered.compile()
            cb = collective_bytes(compiled.as_text())
            print('COLLECTIVE_TOTAL', cb['total'])
            ma = compiled.memory_analysis()
            print('PEAK', getattr(ma, 'temp_size_in_bytes', -1))
        """)
        assert "COLLECTIVE_TOTAL" in out


class TestProductionDryrunArtifact:
    """Validate the recorded 512-device dry-run artifact (produced by
    repro.launch.dryrun; this asserts on its contents rather than re-running
    the multi-minute compiles inside pytest)."""

    ART = os.path.join(REPO, "benchmarks", "artifacts", "dryrun.jsonl")

    def _records(self):
        if not os.path.exists(self.ART):
            pytest.skip("dry-run artifact not generated yet")
        recs = [json.loads(l) for l in open(self.ART) if l.strip()]
        best = {}
        for r in recs:  # keep the latest record per cell
            best[(r["arch"], r["shape"], r["mesh"])] = r
        return best

    def test_single_pod_all_cells_resolved(self):
        best = self._records()
        cells = [(a, s, m) for (a, s, m) in best if m == "16x16"]
        if len(cells) < 40:
            pytest.skip("single-pod sweep incomplete")
        statuses = {k: best[k]["status"] for k in cells}
        bad = {k: v for k, v in statuses.items() if v not in ("ok", "skipped")}
        assert not bad, bad

    def test_ok_cells_have_roofline_inputs(self):
        best = self._records()
        for k, r in best.items():
            if r.get("status") != "ok":
                continue
            assert r["cost"].get("flops"), k
            assert "total" in r.get("collectives", {}), k


class TestElasticRemesh:
    def test_checkpoint_remesh_resume(self, tmp_path):
        """Elastic scaling on the SPMD path (DESIGN.md §7): train on a 4x2
        mesh, checkpoint, restore onto a 2x4 mesh with a different plan, and
        continue — loss trajectory stays continuous."""
        out = run_fake_devices(f"""
            import jax, jax.numpy as jnp, numpy as np
            from jax.sharding import AxisType, NamedSharding, PartitionSpec as P
            from repro.checkpoint import restore, save
            from repro.configs import get_config
            from repro.sharding.plans import Plan, activation_rules, param_sharding_tree
            from repro.train import (AdamConfig, DataConfig, TokenPipeline,
                                     init_train_state, make_train_step)

            cfg = get_config('gemma3-4b').reduced()
            opt = AdamConfig(lr=5e-3, warmup_steps=2, total_steps=20)
            data = DataConfig(vocab=cfg.vocab, seq_len=16, global_batch=8, seed=1)

            def build(model_axis, plan_name):
                mesh = jax.make_mesh((8 // model_axis, model_axis), ("data", "model"),
                                     axis_types=(AxisType.Auto,) * 2)
                plan = Plan(plan_name, batch_axes=("data",), tp_axis="model",
                            fsdp_axis=("data",), remat="dots")
                rules = activation_rules(plan, mesh, cfg)
                psh = param_sharding_tree(cfg, plan, mesh)
                ssh = {{'params': psh, 'opt': {{'m': psh, 'v': psh,
                        'step': NamedSharding(mesh, P())}}}}
                step = jax.jit(make_train_step(cfg, plan, opt, rules),
                               in_shardings=(ssh, None), out_shardings=(ssh, None))
                return mesh, ssh, step

            # phase 1: 4x2 mesh
            mesh, ssh, step = build(2, 'ft2')
            state = jax.device_put(init_train_state(cfg, jax.random.PRNGKey(0)), ssh)
            pipe = TokenPipeline(data)
            with mesh:
                for i in range(4):
                    b = {{k: jnp.asarray(v) for k, v in next(pipe).items()}}
                    state, m = step(state, b)
            l4 = float(m['loss'])
            save(r'{tmp_path}', 4, state, meta={{'data': pipe.state()}})

            # phase 2: REMESH to 2x4, restore, continue
            raw, meta = restore(r'{tmp_path}')
            mesh2, ssh2, step2 = build(4, 'ft4')
            state2 = jax.device_put(jax.tree.map(jnp.asarray, raw), ssh2)
            pipe2 = TokenPipeline.restore(data, meta['data'])
            with mesh2:
                for i in range(2):
                    b = {{k: jnp.asarray(v) for k, v in next(pipe2).items()}}
                    state2, m2 = step2(state2, b)
            l6 = float(m2['loss'])
            print('L4', l4, 'L6', l6)
            assert l6 < l4 + 0.5, (l4, l6)  # training continues sanely
        """)
        assert "L6" in out
