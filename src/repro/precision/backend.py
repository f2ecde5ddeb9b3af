"""Precision backend dispatch: one signature, two implementations
(DESIGN.md §6).

Every precision action the bandit selects is *applied* by four ops on
the solver hot path: an elementwise round-to-format (`chop`), a fused
chopped matvec (`chop_mv`), a fused chopped matmul (`chop_matmul` — the
blocked LU trailing update), and a blocked triangular substitution
(`chop_trisolve`). This module gives those ops a backend-agnostic home:

  * ``"jnp"``   — the pure-jnp oracle (`repro.precision.chop`), valid on
    any float carrier (f64 for the paper's host experiments);
  * ``"pallas"``— the Pallas TPU kernels (`kernels/chop`,
    `kernels/qmatmul`, `kernels/trisolve`), compiled, f32 carrier,
    VMEM-resident rounding with no extra HBM round trips. It exists only
    on a TPU: naming it elsewhere raises. ``"pallas-interpret"`` runs
    the same kernels through the Pallas interpreter — the CPU tests'
    bit-exactness tool, never a serving path.

Backends are small frozen dataclasses, so they hash by value and can be
passed as **static jit arguments**: the solvers compile once per
(shapes, config, backend) while the format id stays runtime data —
switching precision actions never recompiles (DESIGN.md §3.4), and
switching backends costs exactly one extra executable.

Bit-exactness contract (DESIGN.md §6.2): for a shared f32 carrier, both
backends produce bit-identical results for `chop` (same integer RNE
algorithm elementwise), `chop_mv` (shared lane-padded row-sum reduction
shape), `chop_matmul` (shared lane-padded K and a single-K-block dot,
whose reduction is M/N-tile-invariant — measured), and `chop_trisolve`
(the kernel performs the oracle `_trisolve_core`'s elementwise ops in
the same order). The multi-K-tile MXU schedule lives on as
`kernels/qmatmul.qmatmul_op` outside the backend contract.

Selection order: explicit argument > `set_default_backend` >
``REPRO_PRECISION_BACKEND`` env var > the platform: ``"pallas"`` in a
TPU process, ``"jnp"`` elsewhere. A TPU runs no f64 carrier
(`precision.chop` refuses it while tracing), so the jnp backend serves
there only on an f32 carrier.
"""
from __future__ import annotations

import dataclasses
import os
from typing import Callable, Dict, Optional, Union

import jax
import jax.numpy as jnp

from . import chop as _chop
from . import dw as _dw

ENV_VAR = "REPRO_PRECISION_BACKEND"

# Arrays smaller than this bypass the pallas chop kernel: the O(n) glue
# vectors inside solver loops are launch-overhead-bound, and the two
# implementations are bit-identical, so routing is a pure perf choice.
DEFAULT_CHOP_MIN_ELEMS = 4096


class PrecisionBackend:
    """Interface shared by all precision backends (duck-typed; this base
    class only documents the contract and hosts shared helpers).

    `carrier_dtype` is the float dtype the backend's solver entry points
    coerce operands to (None = keep the caller's carrier). `host_dtype`
    is the float dtype those entry points take from the host: a flush's
    rows are stacked in it (`tasks.base.stack_fixed`), so a carrier cast
    it implies happens in numpy, before the transfer."""

    name: str = "abstract"
    carrier_dtype: Optional[str] = None

    @property
    def host_dtype(self) -> Optional[str]:
        """The dtype a flush's float rows are stacked in on the host
        (None = as given)."""
        return None

    def chop(self, x: jnp.ndarray, fmt_id) -> jnp.ndarray:
        raise NotImplementedError

    def chop_mv(self, A: jnp.ndarray, v: jnp.ndarray, fmt_id, *,
                chop_output: bool = True) -> jnp.ndarray:
        raise NotImplementedError

    def chop_matmul(self, a: jnp.ndarray, b: jnp.ndarray, fmt_id, *,
                    chop_inputs: bool = True,
                    chop_output: bool = True) -> jnp.ndarray:
        raise NotImplementedError

    def chop_trisolve(self, Lu: jnp.ndarray, b: jnp.ndarray, fmt_id, *,
                      lower: bool, block: int = 128) -> jnp.ndarray:
        """Blocked triangular substitution on the combined LU matrix
        (strictly-lower + unit diagonal when `lower`, upper triangle
        including the diagonal otherwise) — DESIGN.md §6.2/§6.4."""
        raise NotImplementedError

    @property
    def carrier(self) -> str:
        """The carrier's label in metrics: the dtype solves are coerced
        to, or "native" (the caller's)."""
        return self.carrier_dtype or "native"

    def coerce(self, *arrays: jnp.ndarray):
        """Cast float arrays to this backend's carrier dtype (no-op when
        `carrier_dtype` is None)."""
        if self.carrier_dtype is None:
            return arrays if len(arrays) != 1 else arrays[0]
        dt = jnp.dtype(self.carrier_dtype)
        out = tuple(a.astype(dt) if jnp.issubdtype(jnp.asarray(a).dtype,
                                                   jnp.floating) else a
                    for a in arrays)
        return out if len(out) != 1 else out[0]


@dataclasses.dataclass(frozen=True)
class JnpBackend(PrecisionBackend):
    """Pure-jnp oracle backend: the paper-faithful reference semantics on
    any float carrier. This is the default and the ground truth the
    pallas backend is bit-validated against."""

    name: str = dataclasses.field(default="jnp", init=False)
    carrier_dtype: Optional[str] = None

    def chop(self, x, fmt_id):
        return _chop.chop(x, fmt_id)

    def chop_mv(self, A, v, fmt_id, *, chop_output: bool = True):
        # Same reduction shape as kernels/qmatmul.qmv_op: lane-padded
        # row-sum (see ref.qmv_ref; the import is deferred so that
        # importing repro.precision never pulls in pallas).
        from repro.kernels.qmatmul.ref import qmv_ref
        return qmv_ref(A, v, fmt_id, chop_out=chop_output)

    def chop_matmul(self, a, b, fmt_id, *, chop_inputs: bool = True,
                    chop_output: bool = True):
        # Pinned tiled-reduction contract shared with the pallas kernel:
        # lane-padded K, single carrier dot (DESIGN.md §6.2).
        from repro.kernels.qmatmul.ref import qgemm_ref
        return qgemm_ref(a, b, fmt_id, chop_out=chop_output,
                         chop_inputs=chop_inputs)

    def chop_trisolve(self, Lu, b, fmt_id, *, lower: bool,
                      block: int = 128):
        from repro.kernels.trisolve.ref import trisolve_ref
        return trisolve_ref(Lu, b, fmt_id, lower=lower, block=block)


@dataclasses.dataclass(frozen=True)
class PallasBackend(PrecisionBackend):
    """Pallas TPU fast path: `kernels/chop` for standalone roundings,
    `kernels/qmatmul` for the fused matvec/matmul, `kernels/trisolve`
    for the blocked substitutions. f32 carrier only — solver entry
    points coerce operands via `carrier_dtype`.

    The kernels are compiled for the TPU; `interpret=True` runs them
    through the Pallas interpreter instead (CPU tests). `chop_min_elems`
    routes small glue arrays to the bit-identical jnp chop to avoid
    kernel launch overhead. Its host dtype is the carrier's: rows leave
    the host as f32, and `coerce` finds nothing to cast."""

    name: str = dataclasses.field(default="pallas", init=False)
    carrier_dtype: Optional[str] = "float32"
    interpret: bool = False
    chop_min_elems: int = DEFAULT_CHOP_MIN_ELEMS

    @property
    def host_dtype(self) -> Optional[str]:
        return self.carrier_dtype

    def chop(self, x, fmt_id):
        x = jnp.asarray(x)
        if x.dtype != jnp.float32 or x.size < self.chop_min_elems:
            return _chop.chop(x, fmt_id)
        from repro.kernels.chop import chop_op
        return chop_op(x, fmt_id, interpret=self.interpret)

    def chop_mv(self, A, v, fmt_id, *, chop_output: bool = True):
        from repro.kernels.qmatmul import qmv_op
        return qmv_op(A, v, fmt_id, chop_out=chop_output,
                      interpret=self.interpret)

    def chop_matmul(self, a, b, fmt_id, *, chop_inputs: bool = True,
                    chop_output: bool = True):
        a = jnp.asarray(a)
        b = jnp.asarray(b)
        if (not chop_inputs or a.dtype != jnp.float32
                or b.dtype != jnp.float32):
            # The fused kernel always rounds its operands in VMEM and is
            # f32-only; the oracle shares the pinned reduction contract,
            # so routing there is bit-transparent (DESIGN.md §6.2).
            from repro.kernels.qmatmul.ref import qgemm_ref
            return qgemm_ref(a, b, fmt_id, chop_out=chop_output,
                             chop_inputs=chop_inputs)
        from repro.kernels.qmatmul import qgemm_op
        return qgemm_op(a, b, fmt_id, chop_out=chop_output,
                        interpret=self.interpret)

    def chop_trisolve(self, Lu, b, fmt_id, *, lower: bool,
                      block: int = 128):
        Lu = jnp.asarray(Lu)
        b = jnp.asarray(b)
        if Lu.dtype != jnp.float32 or b.dtype != jnp.float32:
            # Non-f32 carriers only occur outside the coerced solver
            # entry points; the oracle IS the kernel body, so this
            # routing is bit-transparent (DESIGN.md §6.2).
            from repro.kernels.trisolve.ref import trisolve_ref
            return trisolve_ref(Lu, b, fmt_id, lower=lower, block=block)
        from repro.kernels.trisolve import trisolve_op
        return trisolve_op(Lu, b, fmt_id, lower=lower, block=block,
                           interpret=self.interpret)


@dataclasses.dataclass(frozen=True)
class DoubleWord(PrecisionBackend):
    """The double-word f32 carrier over a base backend (DESIGN.md §13).

    Values that are `dw.DW` pairs carry 48 significand bits, so the
    paper's fp64 steps run on a TPU; their ops are the `dw` module's
    (plain jnp under XLA, exact products, pinned reductions), and
    rounding to fp64 is the identity on the pair. Plain f32 arrays go to
    `base` unchanged: its kernels serve every step in a format of at
    most 24 bits. Frozen and value-hashed like the others, so it rides
    as a static jit argument; the solvers pick their DW paths from its
    type. The host hands it float64 rows (`host_dtype`), which
    `dw.stack_host` splits into (hi, lo) before the transfer: a cast to
    the f32 `carrier_dtype` there would drop every low word."""

    base: PrecisionBackend = dataclasses.field(default_factory=JnpBackend)
    name: str = dataclasses.field(default="double-word", init=False)
    carrier_dtype: Optional[str] = dataclasses.field(default="float32",
                                                     init=False)

    def __post_init__(self):
        if isinstance(self.base, DoubleWord):
            raise TypeError("DoubleWord wraps an f32 backend, not another "
                            "DoubleWord")

    @property
    def carrier(self) -> str:
        return "double-word"

    @property
    def host_dtype(self) -> Optional[str]:
        return "float64"

    def chop(self, x, fmt_id):
        if isinstance(x, _dw.DW):
            return _dw.chop(x, fmt_id)
        return self.base.chop(x, fmt_id)

    def chop_mv(self, A, v, fmt_id, *, chop_output: bool = True):
        if not (isinstance(A, _dw.DW) or isinstance(v, _dw.DW)):
            return self.base.chop_mv(A, v, fmt_id, chop_output=chop_output)
        out = _dw.matvec(_dw.chop(_as_dw(A), fmt_id),
                         _dw.chop(_as_dw(v), fmt_id))
        return _dw.chop(out, fmt_id) if chop_output else out

    def chop_matmul(self, a, b, fmt_id, *, chop_inputs: bool = True,
                    chop_output: bool = True):
        if not (isinstance(a, _dw.DW) or isinstance(b, _dw.DW)):
            return self.base.chop_matmul(a, b, fmt_id,
                                         chop_inputs=chop_inputs,
                                         chop_output=chop_output)
        a, b = _as_dw(a), _as_dw(b)
        if chop_inputs:
            a, b = _dw.chop(a, fmt_id), _dw.chop(b, fmt_id)
        bt = _dw.tmap(lambda m: m.T, b)
        # One output row per step: a DW matvec of b^T with a row of a.
        out = jax.lax.map(lambda row: _dw.matvec(bt, row), a)
        return _dw.chop(out, fmt_id) if chop_output else out

    def chop_trisolve(self, Lu, b, fmt_id, *, lower: bool,
                      block: int = 128):
        if not (isinstance(Lu, _dw.DW) or isinstance(b, _dw.DW)):
            return self.base.chop_trisolve(Lu, b, fmt_id, lower=lower,
                                           block=block)
        b = _dw.chop(_as_dw(b), fmt_id)
        return _dw.chop(_dw.trisolve(_as_dw(Lu), b, lower=lower), fmt_id)


def _as_dw(x):
    return x if isinstance(x, _dw.DW) else _dw.lift(x)


# ---------------------------------------------------------------------------
# Registry + selection
# ---------------------------------------------------------------------------

BackendLike = Union[None, str, PrecisionBackend]

_REGISTRY: Dict[str, Callable[[], PrecisionBackend]] = {
    "jnp": JnpBackend,
    "pallas": PallasBackend,
    "pallas-interpret": lambda: PallasBackend(interpret=True),
}
_DEFAULT: Optional[PrecisionBackend] = None


def register_backend(name: str,
                     factory: Callable[[], PrecisionBackend]) -> None:
    """Register a backend factory under `name` (overwrites allowed)."""
    _REGISTRY[name] = factory


def available_backends():
    return sorted(_REGISTRY)


def _from_name(name: str) -> PrecisionBackend:
    if name not in _REGISTRY:
        raise KeyError(f"unknown precision backend {name!r}; "
                       f"available: {available_backends()}")
    backend = _REGISTRY[name]()
    if (isinstance(backend, PallasBackend) and not backend.interpret
            and jax.default_backend() != "tpu"):
        raise RuntimeError(
            f"precision backend {name!r} compiles its Pallas kernels for "
            f"a TPU, and this process runs on {jax.default_backend()!r}; "
            "name 'pallas-interpret' to run the kernels through the "
            "Pallas interpreter (tests), or 'jnp'")
    return backend


def set_default_backend(backend: BackendLike) -> Optional[PrecisionBackend]:
    """Set the process-wide default backend (None restores env/platform
    resolution). Returns the previous override, for save/restore."""
    global _DEFAULT
    prev = _DEFAULT
    _DEFAULT = (resolve_backend(backend)
                if backend is not None else None)
    return prev


def default_backend() -> PrecisionBackend:
    if _DEFAULT is not None:
        return _DEFAULT
    name = os.environ.get(ENV_VAR) or (
        "pallas" if jax.default_backend() == "tpu" else "jnp")
    return _from_name(name)


def resolve_backend(backend: BackendLike = None) -> PrecisionBackend:
    """Coerce a backend spec (instance | name | None=default) into a
    backend instance. Pure Python — call before tracing so the result
    can be a static jit argument."""
    if backend is None:
        return default_backend()
    if isinstance(backend, str):
        return _from_name(backend)
    return backend
