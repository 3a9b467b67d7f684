"""Fault-tolerance substrate: checkpoint roundtrip / crash consistency /
elastic restore; data-pipeline determinism and resume-exactness."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import ckpt as CKPT
from repro.data import Prefetcher, SyntheticLM
from repro.optim import adamw


def _state(key=0):
    k = jax.random.PRNGKey(key)
    params = {"a": jax.random.normal(k, (8, 16)),
              "nested": {"b": jnp.arange(10, dtype=jnp.int32)}}
    return params, adamw.init(params)


def test_ckpt_roundtrip(tmp_path):
    params, opt = _state()
    CKPT.save(str(tmp_path), 7, (params, opt))
    assert CKPT.latest_step(str(tmp_path)) == 7
    (p2, o2), manifest = CKPT.restore(str(tmp_path), (params, opt))
    assert manifest["step"] == 7
    for a, b in zip(jax.tree.leaves((params, opt)), jax.tree.leaves((p2, o2))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_ckpt_keeps_latest_and_gc(tmp_path):
    params, opt = _state()
    for s in (1, 2, 3, 4, 5):
        CKPT.save(str(tmp_path), s, (params, opt), keep=2)
    steps = sorted(d for d in os.listdir(tmp_path) if d.startswith("step_"))
    assert len(steps) == 2 and CKPT.latest_step(str(tmp_path)) == 5


def test_ckpt_shape_mismatch_rejected(tmp_path):
    params, opt = _state()
    CKPT.save(str(tmp_path), 1, params)
    bad = {"a": jnp.zeros((4, 4)), "nested": {"b": jnp.zeros(10, jnp.int32)}}
    with pytest.raises(ValueError):
        CKPT.restore(str(tmp_path), bad)


def test_ckpt_elastic_restore_new_sharding(tmp_path):
    """Restore onto explicit (trivial, 1-device) NamedShardings — the code
    path the 256→512-chip rescale uses."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    params, _ = _state()
    CKPT.save(str(tmp_path), 3, params)
    from jax.sharding import AxisType

    mesh = jax.make_mesh((1,), ("data",), axis_types=(AxisType.Auto,))
    shardings = jax.tree.map(lambda _: NamedSharding(mesh, P()), params)
    (p2), _ = CKPT.restore(str(tmp_path), params, shardings=shardings)
    np.testing.assert_array_equal(np.asarray(p2["a"]), np.asarray(params["a"]))


def test_data_deterministic_and_resume_exact():
    d1 = SyntheticLM(1024, 64, 8, seed=5)
    d2 = SyntheticLM(1024, 64, 8, seed=5)
    b1, b2 = d1.batch_at(17), d2.batch_at(17)
    np.testing.assert_array_equal(b1["tokens"], b2["tokens"])
    # labels are next-token shifted
    np.testing.assert_array_equal(b1["labels"][:, :-1], b1["tokens"][:, 1:])


def test_data_host_sharding_disjoint_streams():
    a = SyntheticLM(1024, 32, 8, seed=1, host_id=0, num_hosts=2)
    b = SyntheticLM(1024, 32, 8, seed=1, host_id=1, num_hosts=2)
    assert a.local_batch == 4
    assert not np.array_equal(a.batch_at(0)["tokens"], b.batch_at(0)["tokens"])


def test_prefetcher_orders_batches():
    src = SyntheticLM(256, 16, 4, seed=0)
    pf = Prefetcher(src, start_step=10)
    try:
        for expect in (10, 11, 12):
            step, batch = next(pf)
            assert step == expect
            np.testing.assert_array_equal(
                batch["tokens"], src.batch_at(expect)["tokens"]
            )
    finally:
        pf.close()


def test_memmap_pipeline(tmp_path):
    from repro.data import MemmapLM

    path = str(tmp_path / "toks.bin")
    np.arange(100_000, dtype=np.int32).tofile(path)
    d = MemmapLM(path, seq_len=32, global_batch=4)
    b0, b1 = d.batch_at(0), d.batch_at(1)
    assert b0["tokens"].shape == (4, 32)
    assert not np.array_equal(b0["tokens"], b1["tokens"])
    np.testing.assert_array_equal(b0["labels"][:, :-1], b0["tokens"][:, 1:])
