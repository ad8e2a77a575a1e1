"""util/force.force — the read-back completion barrier (PERF.md r4).

On CPU the barrier is trivially satisfied; these tests pin the CONTRACT:
every jax.Array leaf is touched (one launch of one program, one fetch),
non-device leaves and empty arrays are skipped, mixed dtypes survive the
single fetch bit for bit, and a launch that raises reads leaf by leaf."""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from photon_tpu.util.force import force


def test_force_mixed_pytree():
    tree = {
        "a": jnp.arange(10, dtype=jnp.float32),
        "b": (jnp.ones((3, 4), jnp.int32), None),
        "c": np.zeros(5),                       # numpy: no barrier needed
        "d": jnp.zeros((0,), jnp.float32),      # empty: skipped
        "e": "not an array",
        "f": jnp.asarray(2.5, jnp.bfloat16),    # scalar, odd dtype
    }
    force(tree)  # must not raise


def test_force_single_and_bool_leaves():
    force(jnp.ones((1000,), jnp.float32))
    force((jnp.array([True, False]), jnp.arange(3)))
    force(None)
    force({})


def test_force_large_leaf_reads_one_element_only():
    # shape-only check: forcing a big array must not pull it all to host —
    # the implementation reads a 1-element slice; this asserts it runs and
    # the source stays usable afterwards
    x = jnp.arange(1 << 20, dtype=jnp.float32)
    force(x)
    assert float(x[123]) == 123.0


def test_multi_device_detection_defaults_to_host_resident():
    """A leaf without a working ``.devices()`` must be treated as
    host-resident (reading it is free), NOT as sharded — the old
    assume-sharded default silently routed whole mixed trees onto the
    one-round-trip-per-leaf fallback (ADVICE r5 #3)."""
    from photon_tpu.util.force import _multi_device

    class NoDevices:
        def devices(self):
            raise AttributeError("host-resident wrapper")

    assert _multi_device(NoDevices()) is False
    assert _multi_device(jnp.arange(4.0)) is False  # single device

    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from photon_tpu.parallel.mesh import make_mesh

    mesh = make_mesh(num_data=len(jax.devices()))
    sharded = jax.device_put(
        np.arange(16, dtype=np.float32), NamedSharding(mesh, P("data"))
    )
    assert _multi_device(sharded) is (len(jax.devices()) > 1)


def test_force_single_fetch_for_single_device_leaves(monkeypatch):
    """≥2 single-device leaves must take the one-program SINGLE-fetch path
    (one launch, one blocking round trip), even in a tree mixed with
    numpy leaves."""
    from photon_tpu.util import force as force_mod

    calls = []
    orig = force_mod._first_elements

    def counting(leaves):
        calls.append(len(leaves))
        return orig(leaves)

    monkeypatch.setattr(force_mod, "_first_elements", counting)
    force(
        {
            "a": jnp.arange(4, dtype=jnp.float32),
            "b": jnp.ones((3,), jnp.int32),
            "c": np.zeros(5),  # host leaf must not break the fast path
        }
    )
    assert calls == [2]


def test_force_holds_when_the_one_program_cannot_launch(monkeypatch):
    """A launch that raises (leaves on different devices or platforms)
    falls back to a fetch per leaf: the barrier still holds, and the
    per-leaf read gives the same first elements."""
    from photon_tpu.util import force as force_mod

    def refuse(leaves):
        raise ValueError("mixed devices")

    leaves = (jnp.arange(4.0) + 1, jnp.asarray(True), jnp.asarray(2.5, jnp.bfloat16))
    monkeypatch.setattr(force_mod, "_first_elements", refuse)
    assert force_mod._launch(leaves) is None
    force(leaves)  # must not raise
    got = force_mod._read(None, leaves)
    assert got.dtype == np.float32
    assert got.tolist() == [1.0, 1.0, 2.5]


def test_first_elements_is_the_eager_read_bit_for_bit():
    from photon_tpu.util.force import _first_elements

    leaves = (
        jnp.asarray(1.0 + 2.0 ** -30, jnp.float64),
        jnp.asarray([False, True]),
        jnp.asarray([[2 ** 24 + 1, 3]], jnp.int32),
        jnp.asarray(-0.0, jnp.float32),
        jnp.asarray(2.5, jnp.bfloat16),
    )
    want = jnp.concatenate(
        [x.reshape(-1)[0:1].astype(jnp.float32) for x in leaves]
    )
    got = _first_elements(leaves)
    assert got.dtype == jnp.float32 and got.shape == (5,)
    np.testing.assert_array_equal(
        np.asarray(got).view(np.uint32), np.asarray(want).view(np.uint32)
    )
