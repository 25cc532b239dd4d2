"""The comparison that decides ``correct`` (copied from chip_smoke.py's
rule, which compared the kernel path with the composed path; here the
other side is the plain float32 reference).

Logits: the largest absolute difference must stay within ``LOGIT_TOL_ULPS``
bf16 ulps (2**-8) of the largest reference logit. The program computes in
bf16 with float32 accumulation: one bf16 rounding is half an ulp of the
value rounded, and the twelve layers' roundings add in quadrature, so a
few ulps of the logit scale is what an honest bf16 forward pass shows; a
pass in a lower precision (fp8, or int8 activations) or a dropped term
lands far outside.

Tokens (greedy requests only): random weights give nearly flat logits, so
exact argmax ties are routine and bare token equality would flake. A token
that differs from the reference's argmax is excused only where the
reference's top-2 margin is inside the same tolerance.
"""

from __future__ import annotations

import numpy as np

LOGIT_TOL_ULPS = 8


def logit_tolerance(ref_logits: np.ndarray) -> float:
    return LOGIT_TOL_ULPS * 2.0 ** -8 * max(1.0, float(np.abs(ref_logits).max()))


def compare_logits(got: np.ndarray, ref: np.ndarray) -> dict:
    """``got`` and ``ref``: [N, V] rows of logits. -> facts, with ``ok``."""
    tol = logit_tolerance(ref)
    finite = bool(np.isfinite(got).all())
    diff = float(np.abs(got - ref).max()) if finite else float("inf")
    return {"ok": finite and diff <= tol, "max_logit_diff": diff,
            "logit_tol": tol, "rows": int(got.shape[0])}


def greedy_token_ok(token: int, ref_row: np.ndarray, tol: float) -> bool:
    """True when ``token`` is the reference's argmax, or the reference's
    own top-2 margin is inside the tolerance."""
    if int(np.argmax(ref_row)) == int(token):
        return True
    top2 = np.partition(ref_row, -2)[-2:]
    return float(top2[1] - top2[0]) <= tol


def loss_close(got: float, ref: float, rtol: float) -> bool:
    return bool(np.isfinite(got)) and abs(got - ref) <= rtol * abs(ref)
