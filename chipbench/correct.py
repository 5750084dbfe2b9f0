"""The rule that decides ``correct``: measure what the serving precision
costs instead of guessing it.

For a quantity q (a loss, a probability tensor, a gradient tensor)

    dev_sys(q)   = rel-L2 distance of the system's q from the float32
                   reference's
    dev_plain(q) = the same for the reference itself run in the
                   configuration's dtype (bfloat16)

q passes when ``dev_sys <= F * dev_plain + FLOOR``.  A q whose dev_plain
exceeds ILL is ill-conditioned at random init in any implementation of that
precision: it is printed and left out of ``correct``.  Nothing else here is
a tolerance.

F and FLOOR, with what was measured when they were set (my chip runs,
PR 24, seeds 5 and 2147483659; the table is in chipbench/README.md).  On the
training path dev_sys / dev_plain was 0.76-1.14 over every well-conditioned
quantity of both models (XLA compiles the system's graph and the plain bf16
reference into all but the same arithmetic); on the serving path, where the
system runs bucket-padded shapes, 0.80-1.32.  The loss deviations were
4e-4 to 2.3e-3, which is what FLOOR is for.
"""
import numpy as np

F = 2.0        # <= 4: fp8 has five fewer mantissa bits than bf16, a drop in
#                precision moves a deviation by far more than 4x
FLOOR = 0.01   # for quantities such as the loss whose dev_plain is near 0
ILL = 0.5


def rel_l2(got, want):
    got = np.asarray(got, np.float64).ravel()
    want = np.asarray(want, np.float64).ravel()
    return float(np.linalg.norm(got - want) /
                 max(np.linalg.norm(want), 1e-300))


def judge(name, sys_q, ref_q, plain_q):
    """One row of the check: dict with both deviations and the verdict
    ('pass', 'fail' or 'ill')."""
    dev_sys, dev_plain = rel_l2(sys_q, ref_q), rel_l2(plain_q, ref_q)
    if not np.isfinite(dev_sys) or not np.isfinite(dev_plain):
        verdict = "fail"
    elif dev_plain > ILL:
        verdict = "ill"
    else:
        verdict = "pass" if dev_sys <= F * dev_plain + FLOOR else "fail"
    return {"name": name, "dev_sys": dev_sys, "dev_plain": dev_plain,
            "verdict": verdict}


def summarise(rows, must_pass, skip=()):
    """(ok, report): ok when no row outside *skip* fails and every name in
    *must_pass* is well-conditioned and passes."""
    by = {r["name"]: r for r in rows}
    failed = [r for r in rows if r["verdict"] == "fail"
              and r["name"] not in skip]
    missing = [n for n in must_pass
               if by.get(n, {}).get("verdict") != "pass"]
    judged = [r for r in rows if r["verdict"] != "ill"]
    worst = max(judged, key=lambda r: r["dev_sys"] /
                (F * r["dev_plain"] + FLOOR), default=None)
    report = {
        "F": F, "floor": FLOOR, "quantities": len(rows),
        "passed": sum(r["verdict"] == "pass" for r in rows),
        "ill_conditioned": sum(r["verdict"] == "ill" for r in rows),
        "failed": [r for r in rows if r["verdict"] == "fail"],
        "skipped_known_deviations": sorted(set(skip) & set(by)),
        "must_pass_not_passing": missing,
        "closest_to_limit": worst,
    }
    return not failed and not missing, report
