"""Multi-(host-)device behaviour: sharded MoE equivalence, elastic checkpoint
reshard, and a tiny dry-run cell.  Each runs in a subprocess because jax pins
the device count at first init."""

import json
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code: str, devices: int = 8, timeout: int = 420):
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         env=env, capture_output=True, text=True,
                         timeout=timeout)
    assert out.returncode == 0, out.stderr[-4000:]
    return out.stdout


def test_sharded_moe_matches_local():
    print(_run("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.models import moe
        from repro.distributed import sharding as shd
        from repro.launch.mesh import make_local_mesh
        mesh = make_local_mesh(data=2, model=4)
        params = moe.init_moe_params(jax.random.PRNGKey(0), 32, 64, 8)
        x = jax.random.normal(jax.random.PRNGKey(1), (4, 8, 32))
        ref, aux_ref = moe.moe_ffn_local(params, x, top_k=2,
                                         capacity_factor=8.0, act='swiglu')
        shd.set_hint_rules({}, mesh)
        xs = jax.device_put(x, NamedSharding(mesh, P('data', 'model', None)))
        ps = jax.device_put(params, jax.tree.map(
            lambda l: NamedSharding(mesh, P(*((('model',)+(None,)*(l.ndim-1))
                                              if l.ndim == 3
                                              else (None,)*l.ndim))), params))
        out, aux = jax.jit(lambda p, xx: moe.moe_ffn_sharded(
            p, xx, top_k=2, capacity_factor=8.0, act='swiglu',
            mesh=mesh))(ps, xs)
        err = float(jnp.max(jnp.abs(out - ref)))
        assert err < 1e-4, err
        assert abs(float(aux) - float(aux_ref)) < 1e-4
        print('moe ok', err)
    """))


def test_elastic_checkpoint_reshard(tmp_path):
    """Save on a 2×4 mesh, restore onto 4×2 and 1×8 — elastic scaling."""
    code_save = f"""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.checkpoint.manager import CheckpointManager
        from repro.launch.mesh import make_local_mesh
        mesh = make_local_mesh(data=2, model=4)
        w = jnp.arange(64*8, dtype=jnp.float32).reshape(64, 8)
        ws = jax.device_put(w, NamedSharding(mesh, P('data', 'model')))
        CheckpointManager({str(tmp_path)!r}).save(5, {{'w': ws}})
        print('saved')
    """
    print(_run(code_save))
    for d, m in ((4, 2), (1, 8)):
        code_load = f"""
            import jax, jax.numpy as jnp, numpy as np
            from jax.sharding import NamedSharding, PartitionSpec as P
            from repro.checkpoint.manager import CheckpointManager
            from repro.launch.mesh import make_local_mesh
            mesh = make_local_mesh(data={d}, model={m})
            tpl = {{'w': jnp.zeros((64, 8), jnp.float32)}}
            sh = {{'w': NamedSharding(mesh, P('data', 'model'))}}
            tree, step = CheckpointManager({str(tmp_path)!r}).restore(
                tpl, shardings=sh)
            assert step == 5
            got = np.asarray(tree['w'])
            assert np.array_equal(got,
                np.arange(64*8, dtype=np.float32).reshape(64, 8))
            print('restored onto', {d}, 'x', {m})
        """
        print(_run(code_load))


@pytest.mark.slow
def test_dryrun_cell_compiles():
    """One real production-mesh cell end-to-end (512 host devices)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun", "--arch",
         "internlm2-1.8b", "--shape", "decode_32k", "--mesh", "multipod"],
        env=env, capture_output=True, text=True, timeout=600, cwd=REPO)
    assert out.returncode == 0, out.stderr[-4000:]
    assert "OK" in out.stdout
