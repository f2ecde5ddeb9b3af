"""Plain reference of the double-word configuration: the numpy
GMRES-IR of references/gmres_ir.py, unchanged.

Here "fp64" means 48 bits. The program runs its fp64 steps on a
double-word f32 carrier, hi + lo with 24 + 24 significand bits, so the
configuration states `carrier_t` 48 and `correctness.bits` caps every
step at min(T_BITS[name], 48): the reference rounds each fp64 operation
to 48 bits, the precision the program holds, and `refmath.error_bounds`
floors the unit roundoffs at 2^-48. Steps at fp32 and below round to
their own 24, 11 or 8 bits as on the f32 carrier."""
import bench

_plain = bench.module("references", "gmres_ir")
CONVERGED, STAGNATED, MAXITER, FAILED = (
    _plain.CONVERGED, _plain.STAGNATED, _plain.MAXITER, _plain.FAILED)
solve = _plain.solve
