"""Registry-enumerated backend parity + resolver semantics.

Every implementation of every op family registered with
:mod:`repro.core.dispatch` is checked against that family's ``ref``
implementation on the family's example inputs — the parametrization is built
FROM the registry, so registering a new backend (or a whole new op family
with an ``example`` factory) auto-enrolls it here with no hand-maintained
list.  The resolver tests pin the precedence contract: explicit arg (strict,
round-tripping) > force_backend scope > REPRO_BACKEND env > config hint >
capability-ranked auto.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import dispatch

FAMILIES = list(dispatch.list_ops())

PARITY_CASES = [
    pytest.param(fam.name, impl.backend, id=f"{fam.name}-{impl.backend}")
    for fam in FAMILIES
    for impl in fam.impls()
    if impl.backend != dispatch.REF
]


@pytest.fixture(autouse=True)
def _no_env_backend(monkeypatch):
    """Resolution tests must see the real precedence, not CI's env pin."""
    monkeypatch.delenv(dispatch.ENV_VAR, raising=False)


def test_every_family_has_ref_and_example():
    assert FAMILIES, "registry is empty"
    for fam in FAMILIES:
        assert fam.get(dispatch.REF) is not None, f"{fam.name} lacks ref"
        assert fam.example is not None, f"{fam.name} lacks example inputs"


def test_chunked_resolvable_to_ref_and_pallas():
    """Acceptance: the serving hot path has ≥2 registry-resolvable impls."""
    fam = dispatch.get_op("paged_attention_chunked")
    assert fam.resolve("ref").backend == "ref"
    # interpret-mode Pallas must resolve on every platform (CPU included)
    assert fam.resolve("pallas_interpret").backend == "pallas_interpret"


@pytest.mark.parametrize("op_name,backend", PARITY_CASES)
def test_parity_vs_ref(op_name, backend):
    fam = dispatch.get_op(op_name)
    args, kwargs = fam.example()
    spec = dispatch.CallSpec(platform=jax.default_backend(), args=args,
                             kwargs=kwargs)
    impl = fam.get(backend)
    if not impl.supports(spec):
        # Capability-gated impls must refuse explicit selection loudly...
        with pytest.raises(dispatch.BackendUnavailableError):
            fam.resolve(backend, spec=spec)
        # ...and never be chosen by auto.
        assert fam.resolve(spec=spec).backend != backend
        pytest.skip(f"{backend} unsupported on {spec.platform}")
    ref = fam(*args, backend=dispatch.REF, **kwargs)
    out = fam(*args, backend=backend, **kwargs)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("fam", FAMILIES, ids=lambda f: f.name)
def test_explicit_resolution_round_trips(fam):
    """resolve(name).backend == name for every supported impl (the guarantee
    that killed the old double dispatch)."""
    args, kwargs = fam.example()
    spec = dispatch.CallSpec(platform=jax.default_backend(), args=args,
                             kwargs=kwargs)
    for impl in fam.impls():
        if impl.supports(spec):
            assert fam.resolve(impl.backend, spec=spec).backend == impl.backend


def test_auto_never_picks_pallas_on_cpu():
    if jax.default_backend() == "tpu":
        pytest.skip("CPU-only check")
    for fam in FAMILIES:
        assert fam.resolve().backend not in ("pallas", "pallas_interpret"), \
            fam.name


def test_precedence_scope_over_env_over_config(monkeypatch):
    fam = dispatch.get_op("paged_attention")
    # config hint is the weakest preference
    assert fam.resolve(config="ref").backend == "ref"
    monkeypatch.setenv(dispatch.ENV_VAR, "pallas_interpret")
    assert fam.resolve(config="ref").backend == "pallas_interpret"
    with dispatch.force_backend("ref"):
        assert fam.resolve(config="xla").backend == "ref"
        # explicit arg still beats the scope
        assert fam.resolve("xla").backend == "xla"


def test_unsupported_preference_falls_back_to_auto(monkeypatch):
    """A preference naming a registered backend that rejects the call
    raises like an explicit request — no silent re-deciding at any level.
    Only a name the family does not register falls through to auto."""
    if jax.default_backend() == "tpu":
        pytest.skip("CPU-only check")
    fam = dispatch.get_op("paged_attention")
    with dispatch.force_backend("pallas"):
        with pytest.raises(dispatch.BackendUnavailableError):
            fam.resolve()                           # scope
    with pytest.raises(dispatch.BackendUnavailableError):
        fam.resolve(config="pallas")                # config hint
    with pytest.raises(dispatch.BackendUnavailableError):
        fam.resolve("pallas")                       # explicit
    monkeypatch.setenv(dispatch.ENV_VAR, "pallas")
    with pytest.raises(dispatch.BackendUnavailableError):
        fam.resolve()                               # env
    monkeypatch.delenv(dispatch.ENV_VAR)
    # an unregistered name in this family is no claim about the device
    assert "sharded" not in fam.backends()
    with dispatch.force_backend("sharded"):
        assert fam.resolve().backend == "xla"


def test_shape_capability_fallback():
    """stream pallas tiling needs whole 128-lane rows; a ragged array must
    fall back to ref under auto and refuse explicit pallas selection."""
    fam = dispatch.get_op("stream_add")
    a = jnp.ones((100,), jnp.float32)               # not a multiple of 128
    spec = dispatch.CallSpec(platform=jax.default_backend(), args=(a, a),
                             kwargs={})
    assert fam.resolve(spec=spec).backend == "ref"
    with pytest.raises(dispatch.BackendUnavailableError):
        fam.resolve("pallas_interpret", spec=spec)


def test_resolution_log_records_op_and_backend():
    fam = dispatch.get_op("vector_gather")
    args, kwargs = fam.example()
    with dispatch.record_resolutions() as log:
        fam(*args, backend="ref", **kwargs)
    assert ("vector_gather", "ref") in log


def test_nested_resolution_logs_stay_separate():
    """Exiting an inner record_resolutions scope must not drop the outer
    (removal is by identity — two empty logs compare equal)."""
    with dispatch.record_resolutions() as outer:
        with dispatch.record_resolutions() as inner:
            pass
        dispatch.resolve("vector_gather", "ref")
    assert ("vector_gather", "ref") in outer
    assert inner == []


def test_duplicate_registration_rejected():
    fam = dispatch.get_op("stream_add")
    with pytest.raises(ValueError):
        fam.register("ref")(lambda *a, **k: None)
    with pytest.raises(ValueError):
        fam.register("not_a_backend")(lambda *a, **k: None)
