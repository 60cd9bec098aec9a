"""The comparison that decides ``correct``.

Once the window has closed and the server's state is freed, a sample of
the request-steps that landed in the window, drawn from the seed a whole
batch-step at a time, is run again through the configuration's plain
float32 reference, teacher-forced:
the reference starts from the latents the server streamed for the step
before and makes one step.  For each sampled step

    rel_err = ||x_served - x_ref|| / ||b * m_ref||

where ``x_ref = a x_prev + b m_ref`` is the reference sampler's update with
the reference model's output ``m_ref``, so the number is the error of the
model's contribution, not of the latents it is added to.  The run's number
is the worst over the sample; its limit is the configuration's
``limits.rel_err``.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import jax.numpy as jnp
import numpy as np


def eligible(steps: int, ripple: Dict,
             only: Optional[List[int]] = None) -> List[int]:
    """Steps worth comparing: those with a streamed predecessor, and, where
    the traffic snaps, only those Eq. 4 snaps at, so the check always
    covers the reuse path; of those, the traffic's ``compare_steps``
    where it names some."""
    from bench.refops import eq4_theta

    out = [s for s in range(1, steps)]
    snapping = [s for s in out if eq4_theta(s, steps, ripple) > 0]
    out = snapping or out
    return [s for s in out if s in only] if only else out


def batch_steps(landed: List[Tuple[float, int, int]], tol: float = 0.05
                ) -> List[List[Tuple[float, int, int]]]:
    """Landings ``(time, request, step)`` grouped into batch-steps: a
    batch publishes every request's latents for a step together, so its
    landings share the step and follow each other within ``tol``
    seconds."""
    groups: List[List[Tuple[float, int, int]]] = []
    for land in sorted(landed):
        g = groups[-1] if groups else None
        if g and land[0] - g[-1][0] <= tol and land[2] == g[0][2]:
            g.append(land)
        else:
            groups.append([land])
    return groups


def draw(landed: List[Tuple[float, int, int]], steps: int, ripple: Dict,
         count: int, seed: int, only: Optional[List[int]] = None
         ) -> List[Tuple[int, int]]:
    """At least ``count`` request-steps ``(request, step)`` from those that
    landed in the window, drawn from the seed a whole batch-step at a
    time, so that every slot of a drawn batch is compared."""
    ok = set(eligible(steps, ripple, only))
    pool = [g for g in batch_steps(landed) if g[0][2] in ok]
    rng = np.random.default_rng([int(seed) % 2 ** 63, 1])
    out: List[Tuple[int, int]] = []
    for i in rng.permutation(len(pool)):
        if len(out) >= count:
            break
        out.extend((r, s) for _, r, s in pool[i])
    return sorted(out)


def gap(x_served, x_prev, m_ref, a: float, b: float) -> float:
    x_ref = a * jnp.asarray(x_prev, jnp.float32) + b * m_ref
    num = jnp.linalg.norm(jnp.asarray(x_served, jnp.float32) - x_ref)
    return float(num / jnp.linalg.norm(b * m_ref))


def compare(ref, key, model: Dict, ripple: Dict, steps: int,
            samples: List[Tuple[int, int]], chunks: Dict, txts: Dict,
            control: bool = False) -> Dict[str, float]:
    """Worst ``rel_err`` over the sample (and, with ``control``, the worst
    gap of the float8 control in the server's place)."""
    worst: Dict[str, float] = {}
    for rid, step in samples:
        x_prev, x_served = chunks[rid][step - 1], chunks[rid][step]
        m_ref, a, b = ref.model_out(key, model, ripple, x_prev, step, steps,
                                    txts[rid])
        readings = {"rel_err": gap(x_served, x_prev, m_ref, a, b)}
        if control:
            m_ctl, _, _ = ref.model_out(key, model, ripple, x_prev, step,
                                        steps, txts[rid], quant="fp8")
            x_ctl = a * jnp.asarray(x_prev, jnp.float32) + b * m_ctl
            readings["control_rel_err"] = gap(x_ctl, x_prev, m_ref, a, b)
        for k, v in readings.items():
            # NaN compares false, so a non-finite reading must win here.
            if not np.isfinite(v) or not v <= worst.get(k, -1.0):
                worst[k] = v
    return worst


def verdict(worst: Dict[str, float], limits: Dict[str, float]
            ) -> Tuple[bool, Dict[str, Dict[str, Optional[float]]]]:
    """(correct, {name: {value, limit}}) for the numbers held to limits."""
    checks = {}
    ok = bool(worst)
    for name, limit in limits.items():
        v = worst.get(name)
        checks[name] = {"value": v, "limit": limit}
        ok = ok and v is not None and np.isfinite(v) and v <= limit
    return ok, checks
