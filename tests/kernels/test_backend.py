"""The kernel backend follows the platform; only ``ops.use_backend`` scopes
another (the interpret-mode sweeps ask for ``pallas_interpret`` with it)."""

import jax
import pytest

from repro.kernels import ops


def test_cpu_runs_the_jnp_reference():
    assert jax.default_backend() == "cpu"
    assert ops.get_backend() == "jnp"


def test_tpu_runs_the_pallas_kernels(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert ops.get_backend() == "pallas"
    with ops.use_backend("jnp"):
        assert ops.get_backend() == "jnp"
    assert ops.get_backend() == "pallas"


def test_use_backend_nests_and_restores():
    with ops.use_backend("pallas_interpret"):
        assert ops.get_backend() == "pallas_interpret"
        with ops.use_backend("jnp"):
            assert ops.get_backend() == "jnp"
        assert ops.get_backend() == "pallas_interpret"
    assert ops.get_backend() == "jnp"


def test_use_backend_restores_when_the_body_raises():
    with pytest.raises(RuntimeError):
        with ops.use_backend("pallas_interpret"):
            raise RuntimeError("boom")
    assert ops.get_backend() == "jnp"


def test_use_backend_rejects_unknown_names():
    with pytest.raises(ValueError, match="not in"):
        with ops.use_backend("cuda"):
            pass
    assert ops.get_backend() == "jnp"
