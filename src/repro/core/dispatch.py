"""Unified operator-backend registry: ONE dispatch API from kernels to serving.

The paper's central observation is that performance portability lives in the
software layer that maps operators onto hardware backends.  Before this module
that mapping was re-implemented per file: every ``kernels/*/ops.py`` had its
own ``backend="auto"|"ref"|"interpret"`` string ladder, the ``core/*_api.py``
wrappers layered a second (inconsistent) ladder on top, and the serving engine
hardcoded one implementation.  This registry is the single place where

  * implementations of an op family are **registered** under a backend name,
  * each implementation carries a **capability predicate** (platform, dtype,
    shape constraints) and a rank used by auto selection,
  * a **resolver** picks the implementation with a well-defined precedence.

Backend names
-------------
``ref``               pure-jnp oracle (any platform, always available)
``xla``               jnp form tuned for XLA (e.g. segment-softmax BlockList)
``pallas``            compiled Pallas kernel (TPU only)
``pallas_interpret``  the same kernel in interpret mode (any platform; slow —
                      never chosen by auto, used for validation)
``sharded``           shard_map scale-out form (per-shard partials + mesh
                      collectives); gated on mesh presence (a ``mesh=`` hint
                      in the CallSpec kwargs, or >1 local device) and never
                      auto-preferred over the single-device forms — a mesh
                      is something a caller opts into, not a faster kernel

Resolution precedence (highest wins)
------------------------------------
1. explicit ``backend=`` argument at the call site — **strict**: if the named
   implementation is missing or its capability predicate rejects the call,
   :class:`BackendUnavailableError` is raised (no silent re-deciding);
2. ``with force_backend("..."):`` scope;
3. the ``REPRO_BACKEND`` environment variable;
4. a config hint (e.g. ``ServeConfig.backend``) passed by the caller;
5. capability-ranked auto: the supported implementation with the highest rank.

Levels 2–4 are *preferences* only in that a family which has no
implementation under the preferred name resolves by auto ranking.  A preferred
backend that IS registered but whose capability predicate rejects the call
raises :class:`BackendUnavailableError`, exactly like an explicit request: so
``REPRO_BACKEND=pallas`` on a host where JAX found no TPU fails loudly instead
of serving with another backend on the CPU.
Every resolution is appended to the active :func:`record_resolutions` scope so
benchmarks can attribute numbers to the implementation that actually ran.

``jax.jit`` plumbing lives here too: implementations are registered already
jitted (with their own static argnames); the resolver runs host-side — either
outside jit or at trace time — so the backend name never becomes a traced
value.
"""
from __future__ import annotations

import contextlib
import dataclasses
import os
import threading
from typing import Any, Callable, Dict, Iterator, List, Optional, Sequence, Tuple

import jax

__all__ = [
    "REF", "XLA", "PALLAS", "PALLAS_INTERPRET", "SHARDED", "BACKENDS",
    "ENV_VAR", "BackendUnavailableError", "CallSpec", "Impl", "OpFamily",
    "op", "get_op", "list_ops", "resolve", "force_backend", "forced_backend",
    "record_resolutions", "on_tpu", "mesh_present",
]

REF = "ref"
XLA = "xla"
PALLAS = "pallas"
PALLAS_INTERPRET = "pallas_interpret"
SHARDED = "sharded"
BACKENDS = (REF, XLA, PALLAS, PALLAS_INTERPRET, SHARDED)

ENV_VAR = "REPRO_BACKEND"

# Auto selection picks the highest-ranked *supported* implementation.
# pallas_interpret ranks below everything: it is a validation tool, orders of
# magnitude slower than the jnp forms — only an explicit request selects it.
# sharded sits below ref: scale-out is opted into (a mesh-holding caller
# resolves it explicitly), auto keeps picking the single-device forms even on
# multi-device hosts.
_DEFAULT_RANK = {PALLAS: 30, XLA: 20, REF: 10, SHARDED: 5,
                 PALLAS_INTERPRET: 0}

_AUTO_NAMES = (None, "auto", "")


class BackendUnavailableError(ValueError):
    """An explicitly requested backend is missing or rejects the call."""


@dataclasses.dataclass(frozen=True)
class CallSpec:
    """What the resolver knows about one call site.

    ``args``/``kwargs`` are the actual call operands (possibly tracers, or
    empty when resolving ahead of any call, as the serving engine does at
    init); capability predicates must treat missing operands as "supported"
    and only reject on positive evidence.  :func:`mesh_present` is the one
    deliberate exception: a ``sharded`` impl is uncallable without a device
    fabric, and "one local device and no mesh hint" IS positive evidence of
    its absence — callers resolving ``sharded`` ahead of a call must carry
    their mesh in ``kwargs`` (the sharded serving engine does).
    """

    platform: str                                  # "cpu" | "tpu" | "gpu"
    args: Tuple[Any, ...] = ()
    kwargs: Dict[str, Any] = dataclasses.field(default_factory=dict)


def on_tpu(spec: CallSpec) -> bool:
    """Capability predicate for compiled Pallas kernels."""
    return spec.platform == "tpu"


def mesh_present(spec: CallSpec) -> bool:
    """Capability predicate for ``sharded`` (shard_map) implementations.

    Positive evidence of a mesh: the caller resolved with a ``mesh=`` kwarg
    in its :class:`CallSpec` (the serving engine does, at init), or the host
    exposes more than one local device (``XLA_FLAGS=
    --xla_force_host_platform_device_count`` sweeps, real multi-chip hosts).
    A bare single-device call rejects, so the parity suite skips the
    collective path where no collective can run.
    """
    if spec.kwargs.get("mesh") is not None:
        return True
    return len(jax.devices()) > 1


def _always(spec: CallSpec) -> bool:
    return True


@dataclasses.dataclass(frozen=True)
class Impl:
    """One registered implementation of an op family."""

    op: str
    backend: str
    fn: Callable
    supports: Callable[[CallSpec], bool]
    rank: int

    def __call__(self, *args: Any, **kwargs: Any) -> Any:
        return self.fn(*args, **kwargs)


# --------------------------------------------------------------------------
# Scoped override + resolution log (thread-local so jit tracing in worker
# threads can't leak scopes across tests).
# --------------------------------------------------------------------------
_STATE = threading.local()


def _scope_stack() -> List[str]:
    if not hasattr(_STATE, "forced"):
        _STATE.forced = []
    return _STATE.forced


def _log_stack() -> List[List[Tuple[str, str]]]:
    if not hasattr(_STATE, "logs"):
        _STATE.logs = []
    return _STATE.logs


@contextlib.contextmanager
def force_backend(name: Optional[str]) -> Iterator[None]:
    """Scoped backend preference (``None``/"auto" is a no-op scope)."""
    stack = _scope_stack()
    stack.append(name if name is not None else "auto")
    try:
        yield
    finally:
        stack.pop()


def forced_backend() -> Optional[str]:
    """The innermost non-auto ``force_backend`` scope, if any."""
    for name in reversed(_scope_stack()):
        if name not in _AUTO_NAMES:
            return name
    return None


@contextlib.contextmanager
def record_resolutions() -> Iterator[List[Tuple[str, str]]]:
    """Collect ``(op, backend)`` pairs resolved inside the scope."""
    log: List[Tuple[str, str]] = []
    _log_stack().append(log)
    try:
        yield log
    finally:
        # Remove by IDENTITY — list.remove() compares by equality and two
        # empty logs are ==, so nested scopes would drop the wrong one.
        stack = _log_stack()
        for i in range(len(stack) - 1, -1, -1):
            if stack[i] is log:
                del stack[i]
                break


def _note(op_name: str, backend: str) -> None:
    for log in _log_stack():
        log.append((op_name, backend))


# --------------------------------------------------------------------------
# Op families
# --------------------------------------------------------------------------
class OpFamily:
    """A named operator with one or more backend implementations.

    Calling the family resolves and invokes in one step::

        out = flash_op(q, k, v, causal=True, backend=None)

    All implementations of a family share one call signature; per-backend
    extras (tile sizes, interpret flags) are baked in at registration.

    ``tunables`` declares the family's cross-backend performance knobs as
    ``{name: default}`` — keyword-only ints every implementation accepts
    (backends that have no use for one simply ``del`` it).  Declaring them
    here (instead of in each ops.py) gives benchmarks and metrics one place
    to enumerate what can be swept and what the defaults are; the values
    themselves still travel as ordinary static kwargs.
    """

    def __init__(self, name: str, *, doc: str = "",
                 example: Optional[Callable[[], Tuple[tuple, dict]]] = None,
                 tunables: Optional[Dict[str, Any]] = None):
        self.name = name
        self.doc = doc
        # Example-input factory: ``() -> (args, kwargs)`` with shapes small
        # enough for interpret mode.  Powers the registry-enumerated parity
        # suite — no hand-maintained op list in tests.
        self.example = example
        self.tunables: Dict[str, Any] = dict(tunables or {})
        self._impls: Dict[str, Impl] = {}

    # ------------------------------------------------------------- registry
    def register(self, backend: str, *, rank: Optional[int] = None,
                 supports: Optional[Callable[[CallSpec], bool]] = None,
                 ) -> Callable[[Callable], Callable]:
        """Decorator: register ``fn`` as this op's ``backend`` implementation.

        ``supports`` defaults to platform=="tpu" for ``pallas``, mesh
        presence for ``sharded`` and to always-true otherwise; compose extra
        shape/dtype constraints by passing a predicate (it replaces, not
        augments, the default — include :func:`on_tpu` /
        :func:`mesh_present` yourself for those backends).
        """
        if backend not in BACKENDS:
            raise ValueError(f"unknown backend {backend!r}; one of {BACKENDS}")
        if backend in self._impls:
            raise ValueError(f"{self.name}: backend {backend!r} registered twice")

        def deco(fn: Callable) -> Callable:
            pred = supports
            if pred is None:
                pred = {PALLAS: on_tpu, SHARDED: mesh_present}.get(
                    backend, _always)
            self._impls[backend] = Impl(
                op=self.name, backend=backend, fn=fn, supports=pred,
                rank=_DEFAULT_RANK[backend] if rank is None else rank)
            return fn

        return deco

    def impls(self) -> List[Impl]:
        """All implementations, highest rank first."""
        return sorted(self._impls.values(), key=lambda i: -i.rank)

    def backends(self) -> List[str]:
        return [i.backend for i in self.impls()]

    def get(self, backend: str) -> Optional[Impl]:
        return self._impls.get(backend)

    # -------------------------------------------------------------- resolve
    def resolve(self, backend: Optional[str] = None, *,
                config: Optional[str] = None,
                spec: Optional[CallSpec] = None) -> Impl:
        """Pick the implementation for one call (see module precedence)."""
        if not self._impls:
            raise BackendUnavailableError(f"op {self.name!r} has no backends")
        if spec is None:
            spec = CallSpec(platform=jax.default_backend())

        if backend not in _AUTO_NAMES:                 # 1. explicit — strict
            impl = self._impls.get(backend)
            if impl is None:
                raise BackendUnavailableError(
                    f"{self.name}: backend {backend!r} not registered "
                    f"(have {self.backends()})")
            if not impl.supports(spec):
                raise BackendUnavailableError(
                    f"{self.name}: backend {backend!r} rejects this call "
                    f"(platform {spec.platform!r}, or its shapes)")
            # The resolved name must round-trip an explicit request — this is
            # the single-resolver guarantee that killed the old double
            # dispatch (pallas request silently re-deciding to ref).
            assert impl.backend == backend, (impl.backend, backend)
            self._note(impl)
            return impl

        for pref in (forced_backend(),                 # 2. scope
                     os.environ.get(ENV_VAR),          # 3. env
                     config):                          # 4. config hint
            if pref in _AUTO_NAMES:
                continue
            impl = self._impls.get(pref)
            if impl is None:
                continue          # no such implementation in this family
            if not impl.supports(spec):
                raise BackendUnavailableError(
                    f"{self.name}: preferred backend {pref!r} rejects this "
                    f"call (platform {spec.platform!r}, or its shapes)")
            self._note(impl)
            return impl

        for impl in self.impls():                      # 5. ranked auto
            if impl.supports(spec):
                self._note(impl)
                return impl
        raise BackendUnavailableError(
            f"{self.name}: no registered backend supports this call on "
            f"platform {spec.platform!r}")

    def _note(self, impl: Impl) -> None:
        _note(self.name, impl.backend)

    # ----------------------------------------------------------------- call
    def __call__(self, *args: Any, backend: Optional[str] = None,
                 config_backend: Optional[str] = None, **kwargs: Any) -> Any:
        spec = CallSpec(platform=jax.default_backend(), args=args,
                        kwargs=kwargs)
        impl = self.resolve(backend, config=config_backend, spec=spec)
        return impl.fn(*args, **kwargs)


_REGISTRY: Dict[str, OpFamily] = {}


def op(name: str, *, doc: str = "",
       example: Optional[Callable[[], Tuple[tuple, dict]]] = None,
       tunables: Optional[Dict[str, Any]] = None) -> OpFamily:
    """Create (or fetch) the :class:`OpFamily` called ``name``."""
    fam = _REGISTRY.get(name)
    if fam is None:
        fam = _REGISTRY[name] = OpFamily(name, doc=doc, example=example,
                                         tunables=tunables)
    else:
        if doc:
            fam.doc = doc
        if example is not None:
            fam.example = example
        if tunables is not None:
            fam.tunables = dict(tunables)
    return fam


def get_op(name: str) -> OpFamily:
    _ensure_registered()
    try:
        return _REGISTRY[name]
    except KeyError:
        raise KeyError(
            f"unknown op {name!r}; known: {sorted(_REGISTRY)}") from None


def list_ops() -> Sequence[OpFamily]:
    """All op families (importing the registering modules first)."""
    _ensure_registered()
    return [fam for _, fam in sorted(_REGISTRY.items())]


def resolve(name: str, backend: Optional[str] = None, *,
            config: Optional[str] = None,
            spec: Optional[CallSpec] = None) -> Impl:
    """Module-level convenience: ``get_op(name).resolve(...)``."""
    return get_op(name).resolve(backend, config=config, spec=spec)


def _ensure_registered() -> None:
    """Import every module that registers implementations (idempotent)."""
    import repro.core.attention_api       # noqa: F401
    import repro.core.embedding_api       # noqa: F401
    import repro.kernels.batched_embedding.ops  # noqa: F401
    import repro.kernels.flash_attention.ops    # noqa: F401
    import repro.kernels.gather_scatter.ops     # noqa: F401
    import repro.kernels.paged_attention.ops    # noqa: F401
    import repro.kernels.stream.ops             # noqa: F401
