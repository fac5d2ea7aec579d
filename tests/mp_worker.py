"""Worker body for the 2-process CPU integration test.

Launched by tests/test_multiprocess.py with the DMLC bootstrap env set
(the reference's rendezvous protocol, reference communicator.cc:60-96 /
docs/env.md:7-45).  Exercises, for real, the paths round-1 review flagged
as untested under jax.process_count() > 1:

- ``mesh.bootstrap``'s ``jax.distributed.initialize`` branch (DMLC env ->
  coordinator address),
- ``_as_stacked`` building global arrays from per-process host data
  (non-addressable shards),
- ``push_pull_local``'s cross-process denominator logic,
- the hierarchical (dcn = processes) reduction path end-to-end,
- ``broadcast_host`` from a root rank owned by one process.

Asserted against numpy computed locally — i.e. multi-process results must
equal what a single process would compute over the union of contributions.
"""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def main() -> int:
    import jax

    # Workers are CPU processes whatever the environment's default
    # platform is; the pin must precede any backend/distributed touch.
    jax.config.update("jax_platforms", "cpu")

    import byteps_tpu.core.api as api
    from byteps_tpu.comm.collectives import broadcast_host
    from byteps_tpu.comm.mesh import get_comm

    api.init()
    n_proc = jax.process_count()
    assert n_proc == 2, f"expected 2 processes, got {n_proc}"
    pid = jax.process_index()
    comm = get_comm()
    assert comm.n_dcn == 2, f"dcn axis should equal process count: {comm.n_dcn}"
    eng = api._require()

    # --- push_pull_local: sum and average over processes --------------------
    n = 999  # odd: exercises ici padding in the hierarchical path
    x = np.arange(n, dtype=np.float32) + 1000.0 * (pid + 1)
    expect_sum = np.sum(
        [np.arange(n, dtype=np.float32) + 1000.0 * (p + 1)
         for p in range(n_proc)], axis=0)
    out = eng.push_pull_local(x, "mp.sum", op="sum")
    np.testing.assert_allclose(np.asarray(out), expect_sum, rtol=1e-6)
    out = eng.push_pull_local(x, "mp.avg", op="average")
    np.testing.assert_allclose(np.asarray(out), expect_sum / n_proc,
                               rtol=1e-6)

    # --- partitioned path: big tensor split into multiple chunks ------------
    big_n = 100_000  # 400 KB f32 over BYTEPS_PARTITION_BYTES=65536 -> ~7 chunks
    rng = np.random.RandomState(7)  # same stream on both processes
    base = rng.randn(big_n).astype(np.float32)
    big = base * (pid + 1)
    out = eng.push_pull_local(big, "mp.big", op="sum")
    np.testing.assert_allclose(np.asarray(out), base * 3.0, rtol=1e-5,
                               atol=1e-5)

    # --- broadcast from root rank 0 (owned by process 0) --------------------
    b = broadcast_host(comm, x, root=0)
    expect_b = np.arange(n, dtype=np.float32) + 1000.0
    np.testing.assert_allclose(np.asarray(b), expect_b, rtol=1e-6)

    # --- torch adapter surface over two real processes ----------------------
    try:
        import torch
        import byteps_tpu.torch as bps_torch
        t = torch.full((8,), float(pid + 1))
        tout = bps_torch.push_pull(t, average=True, name="mp.torch")
        np.testing.assert_allclose(tout.numpy(), np.full((8,), 1.5),
                                   rtol=1e-6)
    except ImportError:
        pass

    api.shutdown()
    print(f"MP_OK {pid}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
