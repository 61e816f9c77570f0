"""Multi-device numerics: sharded == single-device, elastic restore,
pipeline parallelism, compression. Each case runs in a subprocess with
fake CPU devices (the main test process must keep 1 device)."""

import pytest

from conftest import run_multidevice


def test_loss_invariant_across_meshes_and_strategies():
    out = run_multidevice("""
        import jax, jax.numpy as jnp
        from repro._jax_compat import make_mesh
        from repro.configs import REGISTRY, reduced
        from repro.models import build
        from repro.parallel.axes import ShardingRules, param_sharding, use_rules
        import numpy as np

        cfg = reduced(REGISTRY["qwen2.5-3b"])
        model = build(cfg)
        params = model.init(jax.random.PRNGKey(0))
        toks = jax.random.randint(jax.random.PRNGKey(1), (4, 32), 0, cfg.vocab)
        batch = {"tokens": toks, "labels": jnp.roll(toks, -1, 1)}
        ref = float(model.loss(params, batch))
        for (d, m) in [(2, 4), (4, 2), (8, 1), (1, 8)]:
            for strat in ("dos", "megatron"):
                mesh = make_mesh((d, m), ("data", "model"))
                rules = ShardingRules(mesh, strategy=strat, fsdp=True)
                ps = param_sharding(model.defs, rules)
                with use_rules(rules), mesh:
                    p = jax.device_put(params, ps)
                    got = float(jax.jit(model.loss)(p, batch))
                assert abs(got - ref) < 5e-3, (d, m, strat, got, ref)
        print("MESH_NUMERICS_OK")
    """)
    assert "MESH_NUMERICS_OK" in out


def test_elastic_checkpoint_restore_across_meshes(tmp_path):
    out = run_multidevice(f"""
        import jax, jax.numpy as jnp, numpy as np
        from repro._jax_compat import make_mesh
        from repro.configs import REGISTRY, reduced
        from repro.models import build
        from repro.checkpoint import checkpointer
        from repro.runtime import elastic_restore
        from repro.parallel.axes import ShardingRules, param_sharding

        cfg = reduced(REGISTRY["smollm-135m"])
        model = build(cfg)
        params = model.init(jax.random.PRNGKey(0))
        # save on a (4, 2) mesh
        mesh_a = make_mesh((4, 2), ("data", "model"))
        ps_a = param_sharding(model.defs, ShardingRules(mesh_a, "dos", fsdp=True))
        pa = jax.device_put(params, ps_a)
        checkpointer.save(r"{tmp_path}", 3, pa)
        # restore on a (2, 2) mesh — "lost a pod", half the devices
        mesh_b = make_mesh((2, 2), ("data", "model"))
        ps_b = param_sharding(model.defs, ShardingRules(mesh_b, "dos", fsdp=True))
        pb = elastic_restore(r"{tmp_path}", 3, pa, ps_b)
        for x, y in zip(jax.tree.leaves(pa), jax.tree.leaves(pb)):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
        print("ELASTIC_OK")
    """)
    assert "ELASTIC_OK" in out


def test_pipeline_matches_reference():
    out = run_multidevice("""
        import jax, jax.numpy as jnp, dataclasses
        from repro._jax_compat import make_mesh
        from repro.configs import get_config, reduced
        from repro.models import build
        from repro.parallel.pipeline import make_gpipe_loss
        cfg = dataclasses.replace(reduced(get_config("smollm-135m")), n_layers=4)
        model = build(cfg)
        params = model.init(jax.random.PRNGKey(0))
        mesh = make_mesh((4,), ("pod",))
        toks = jax.random.randint(jax.random.PRNGKey(1), (8, 32), 0, cfg.vocab)
        batch = {"tokens": toks, "labels": jnp.roll(toks, -1, 1)}
        ref = float(model.loss(params, batch))
        loss_fn = make_gpipe_loss(cfg, mesh, n_stages=4, n_microbatches=4)
        with mesh:
            pl = float(jax.jit(loss_fn)(params, batch))
        assert abs(ref - pl) < 1e-4, (ref, pl)
        g = jax.jit(jax.grad(loss_fn))(params, batch)
        assert all(bool(jnp.all(jnp.isfinite(x))) for x in jax.tree.leaves(g))
        print("PIPELINE_OK")
    """, n_devices=4)
    assert "PIPELINE_OK" in out


def test_compressed_grad_sync():
    out = run_multidevice("""
        import jax, jax.numpy as jnp
        from repro._jax_compat import make_mesh
        from repro.parallel.compression import compressed_psum_grads, init_error_state
        mesh = make_mesh((8,), ("data",))
        g = {"w": jnp.linspace(-1, 1, 256).reshape(16, 16)}
        e = init_error_state(g)
        gh, ne = jax.jit(lambda g, e: compressed_psum_grads(g, e, mesh))(g, e)
        err = float(jnp.max(jnp.abs(gh["w"] - g["w"])))
        assert err < 1e-2, err           # int8 quantization error bound
        # error feedback: residual equals what the quantizer dropped
        assert float(jnp.max(jnp.abs(ne["w"]))) < 1e-2
        print("COMPRESS_OK")
    """)
    assert "COMPRESS_OK" in out


def test_dryrun_cell_mini_mesh():
    """End-to-end dry-run machinery on a small mesh-shaped problem:
    lower+compile one reduced arch with full shardings + roofline."""
    out = run_multidevice("""
        import jax, jax.numpy as jnp, dataclasses
        from repro._jax_compat import make_mesh
        from repro.configs import REGISTRY, reduced
        from repro.config import ShapeConfig
        from repro.models import build
        from repro.parallel.axes import ShardingRules, use_rules
        from repro.parallel.plan import make_plan
        from repro.launch.steps import make_train_step, make_serve_step
        from repro.optim import OptConfig
        from repro.analysis.roofline import parse_collectives

        mesh = make_mesh((2, 4), ("data", "model"))
        cfg = reduced(REGISTRY["gemma3-1b"])
        model = build(cfg)
        shape = ShapeConfig("t", 64, 4, "train")
        rules = ShardingRules(mesh, strategy="dos", fsdp=True)
        plan = make_plan(model, shape, rules)
        step = make_train_step(model, OptConfig())
        with use_rules(rules), mesh:
            lowered = jax.jit(step, in_shardings=plan.in_shardings,
                              out_shardings=plan.out_shardings).lower(*plan.abstract)
            compiled = lowered.compile()
        cost = compiled.cost_analysis()
        assert cost.get("flops", 0) > 0
        coll = parse_collectives(compiled.as_text())
        assert coll.wire_bytes > 0  # dOS must produce collectives
        mem = compiled.memory_analysis()
        assert mem.temp_size_in_bytes > 0
        # decode plan lowers too
        shape_d = ShapeConfig("d", 64, 4, "decode")
        plan_d = make_plan(model, shape_d, rules)
        serve = make_serve_step(model)
        with use_rules(rules), mesh:
            c2 = jax.jit(serve, in_shardings=plan_d.in_shardings,
                         out_shardings=plan_d.out_shardings).lower(*plan_d.abstract).compile()
        assert c2.cost_analysis().get("flops", 0) > 0
        print("DRYRUN_MINI_OK")
    """)
    assert "DRYRUN_MINI_OK" in out


def test_moe_expert_parallel_matches_oracle():
    out = run_multidevice("""
        import jax, jax.numpy as jnp
        from repro._jax_compat import make_mesh
        from repro.configs import REGISTRY, reduced
        from repro.models import build
        from repro.models.moe import moe_block
        from repro.parallel.moe_ep import moe_block_ep
        cfg = reduced(REGISTRY["deepseek-moe-16b"])
        model = build(cfg)
        params = model.init(jax.random.PRNGKey(0))
        lp = jax.tree.map(lambda a: a[0], params["layers"])["ffn"]
        mesh = make_mesh((2, 4), ("data", "model"))
        x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, cfg.d_model))
        ref = moe_block(lp, x, cfg)
        with mesh:
            got = jax.jit(lambda p_, x_: moe_block_ep(p_, x_, cfg, mesh))(lp, x)
        err = float(jnp.max(jnp.abs(got - ref)))
        assert err < 1e-4, err
        print("MOE_EP_OK")
    """)
    assert "MOE_EP_OK" in out
