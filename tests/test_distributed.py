"""Multi-device behaviour (8 host devices) via subprocess tests, plus
sharding-rule unit tests that run on the in-process single device.

The subprocess scripts are the promoted bodies of the old
``_selftest()`` blocks that lived in ``core/distributed.py`` and
``core/pregel_dist.py``; the modules themselves carry no test code
anymore.  Deeper sharded-engine coverage (parity, dispatch counting,
mesh-keyed caches) lives in ``tests/test_sharded_engine.py``.
"""
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro.configs import ARCHS
from repro.configs.base import SHAPES_BY_NAME
from repro.models import build, input_specs
from repro.parallel import rules

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_devices_subprocess(code: str, ndev: int = 8):
    """Run ``code`` under XLA_FLAGS=--xla_force_host_platform_device_count."""
    env = dict(os.environ,
               XLA_FLAGS=f"--xla_force_host_platform_device_count={ndev}",
               PYTHONPATH=os.path.join(REPO, "src"))
    return subprocess.run([sys.executable, "-c", code], env=env, cwd=REPO,
                          capture_output=True, text=True, timeout=900)


DISTRIBUTED_SPINNER = """
import numpy as np
from repro.core import SpinnerConfig, generators, metrics, partition
from repro.core.distributed import partition_distributed
from repro.launch.mesh import make_partition_mesh

g = generators.watts_strogatz(4000, 12, 0.2, seed=3)
cfg = SpinnerConfig(k=8, seed=1, max_iters=120)
mesh = make_partition_mesh()
assert mesh.size == 8, mesh
labels, stats = partition_distributed(g, cfg, mesh)
phi = metrics.phi(g, labels)
rho = metrics.rho(g, labels, cfg.k)
print(f"devices=8 iters={stats['iterations']} phi={phi:.3f} rho={rho:.3f} "
      f"shards={stats['edge_shard_sizes']}")
assert phi > 0.3, "distributed LPA failed to find locality"
assert rho < cfg.c + 0.05, "distributed LPA failed balance"
assert sum(stats["edge_shard_sizes"]) == g.num_directed_entries
print("DISTRIBUTED SELFTEST OK")
"""


PREGEL_DIST = """
import numpy as np
from jax.sharding import Mesh
from repro.core import generators, metrics, pregel
from repro.core.pregel_dist import pagerank_distributed
from repro.core.spinner import SpinnerConfig, partition
from repro.launch.mesh import make_partition_mesh

g = generators.watts_strogatz(4000, 12, 0.2, seed=3)
mesh = make_partition_mesh()
ndev = mesh.size
cfg = SpinnerConfig(k=ndev, seed=1)
res = partition(g, cfg, record_history=False)
hash_labels = (np.arange(g.num_vertices) * 2654435761 % ndev).astype(np.int32)

ref = pregel.pagerank(g, res.labels, ndev, iters=10).values
pr_sp, st_sp = pagerank_distributed(g, res.labels, mesh, iters=10)
pr_h, st_h = pagerank_distributed(g, hash_labels, mesh, iters=10)
np.testing.assert_allclose(pr_sp, ref, rtol=1e-4, atol=1e-9)
np.testing.assert_allclose(pr_h, ref, rtol=1e-4, atol=1e-9)
red = 1 - st_sp["halo_true_bytes_per_step"] / st_h["halo_true_bytes_per_step"]
print(f"devices={ndev} halo spinner={st_sp['halo_true_bytes_per_step']}B "
      f"hash={st_h['halo_true_bytes_per_step']}B reduction={red:.1%}")
assert red > 0.3, "spinner should reduce halo traffic"
print("PREGEL_DIST SELFTEST OK")
"""


@pytest.mark.slow
def test_distributed_spinner_8dev():
    r = run_devices_subprocess(DISTRIBUTED_SPINNER)
    assert "DISTRIBUTED SELFTEST OK" in r.stdout, r.stdout + r.stderr


@pytest.mark.slow
def test_distributed_pregel_8dev():
    r = run_devices_subprocess(PREGEL_DIST)
    assert "PREGEL_DIST SELFTEST OK" in r.stdout, r.stdout + r.stderr


class TestShardingRules:
    def _mesh22(self):
        if len(jax.devices()) < 1:
            pytest.skip("no devices")
        import numpy as _np
        dev = _np.asarray(jax.devices()[:1]).reshape(1, 1)
        return jax.sharding.Mesh(dev, ("data", "model"))

    def test_param_rules_cover_all_archs(self):
        mesh = self._mesh22()
        for arch, cfg in ARCHS.items():
            api = build(cfg)
            sh = rules.param_shardings(api.param_specs, mesh)
            n = len(jax.tree.leaves(sh))
            assert n == len(jax.tree.leaves(api.param_specs)), arch

    def test_embed_rule(self):
        mesh = self._mesh22()
        api = build(ARCHS["granite-8b"])
        sh = rules.param_shardings(api.param_specs, mesh)
        spec = sh["embed"].spec
        assert spec[0] == "model"

    def test_batch_rule_replicates_batch1(self):
        # AbstractMesh gives real axis extents without needing 256 devices
        mesh = jax.sharding.AbstractMesh((16, 16), ("data", "model"))
        import jax.numpy as jnp
        from repro.models.common import spec as mkspec
        b = {"token": mkspec(1, dtype=jnp.int32),
             "tokens": mkspec(128, 64, dtype=jnp.int32)}
        sh = rules.batch_shardings(b, mesh)
        assert sh["token"].spec == jax.sharding.PartitionSpec()
        assert sh["tokens"].spec[0] in ("data", ("data",))

    def test_cache_rule_finds_batch_dim(self):
        mesh = self._mesh22()
        import jax.numpy as jnp
        from repro.models.common import spec as mkspec
        cache = mkspec(36, 128, 32768, 8, 128, dtype=jnp.bfloat16)
        sh = rules.cache_shardings(cache, mesh, batch_size=128)
        s = sh.spec
        # batch at dim 1, model on the largest divisible dim (sequence)
        assert s[1] is not None and s[2] == "model"

    def test_all_dryrun_cells_have_valid_input_specs(self):
        for arch, cfg in ARCHS.items():
            for sname, shape in SHAPES_BY_NAME.items():
                from repro.configs.base import cell_is_runnable
                if not cell_is_runnable(cfg, shape):
                    continue
                batch, cache = input_specs(cfg, shape)
                assert "tokens" in batch or "token" in batch, (arch, sname)
                if shape.kind == "decode":
                    assert cache is not None, (arch, sname)
