"""``"op": "dist_cg"``: repeated
``legate_sparse_tpu.parallel.dist_cg(dA, b_i)`` to the configuration's
``rtol`` over a row mesh of the cell's chips.

The operator is built shard-locally by ``dist_diags``, as the
``--distributed`` rendition of ``examples/pde.py`` builds it.  Each
right-hand side is drawn on the device from the seed, already laid out
by rows over the mesh; a solve ends when the gathered solution is on
the host.  The check is the ``cg`` module's."""

from __future__ import annotations

from types import SimpleNamespace

import numpy as np

import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec

from benchmark.ops import cg, common
from benchmark.reference import control, poisson


def _operator(cfg: dict, mesh):
    from legate_sparse_tpu import parallel

    dtype = np.dtype(cfg["dtype"])
    a, g, c, m, n = poisson.coefficients(cfg)

    def off1(i):
        # The x-coupling, zeroed across grid rows (examples/pde.py).
        return jnp.where((i + 1) % m == 0, 0.0, a)

    return parallel.dist_diags(
        [c, off1, off1, g, g], [0, 1, -1, m, -m], shape=(n, n), mesh=mesh,
        dtype=dtype, materialize_ell=bool(cfg["materialize_ell"]))


def setup(run) -> None:
    from legate_sparse_tpu import parallel

    cfg, tr = run.cell.config, run.cell.traffic
    mesh = parallel.make_row_mesh(run.devices)
    dA = _operator(cfg, mesh)
    n = poisson.coefficients(cfg)[4]
    rows = NamedSharding(mesh, PartitionSpec(mesh.axis_names[0]))
    run.state = SimpleNamespace(
        A=dA, n=n, nnz=poisson.nnz(cfg),
        itemsize=np.dtype(cfg["dtype"]).itemsize, key=common.key(run, 0),
        rhs=common.vector_maker(n, cfg["dtype"], tr["rhs"], rows))
    step(run, common.WARM)


def solve(run, b):
    from legate_sparse_tpu import parallel

    cfg, tr = run.cell.config, run.cell.traffic
    if run.control:
        return control.cg_bf16(cfg, b, cfg["rtol"], tr["maxiter"])
    return parallel.dist_cg(run.state.A, b, rtol=cfg["rtol"],
                            maxiter=tr["maxiter"])


def step(run, i: int) -> dict:
    return cg.timed_solve(run, i, solve)


finish = cg.finish
release = cg.release
check = cg.check
