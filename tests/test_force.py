"""util/force.force — the read-back completion barrier (PERF.md r4).

On CPU the barrier is trivially satisfied; these tests pin the CONTRACT:
every jax.Array leaf is touched (one fetch), non-device leaves and empty
arrays are skipped, and mixed dtypes survive the single concatenated
fetch."""
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
    """≥2 single-device leaves must take the concatenated SINGLE-fetch path
    (one blocking round trip), even in a tree mixed with
    numpy leaves."""
    import jax.numpy as jnp_mod

    calls = []
    orig = jnp_mod.concatenate

    def counting(*args, **kwargs):
        calls.append(1)
        return orig(*args, **kwargs)

    monkeypatch.setattr(jnp_mod, "concatenate", counting)
    force(
        {
            "a": jnp.arange(4, dtype=jnp.float32),
            "b": jnp.ones((3,), jnp.int32),
            "c": np.zeros(5),  # host leaf must not break the fast path
        }
    )
    assert len(calls) == 1
