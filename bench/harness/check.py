"""The comparison that decides `correct`.

After the window has closed, the pool's memory peak has been read and
the program's state is freed, every sampled answer (an attend output the
window produced: a seeded reservoir over all of them, each session's
latest, and answers given right after a wake) is recomputed by the
configuration's plain reference from the KV and query the generator
makes again from the seed.  The reference imports nothing of the
program.  The reading is the widest gap, relative to the answer's
largest reference value:

    attend_rel_err = max over answers of  max|out - ref| / max|ref|

The control is the same reference with K and V rounded through a lower
precision than the configuration states; it is read only on request
(`run.py --control 1`) and by the tests, never in the benchmark's runs.
"""

from __future__ import annotations

import math
from collections import defaultdict

import jax.numpy as jnp
import numpy as np

BLOCK = 8           # answers per reference call
CTX_ROUND = 4096    # reference contexts are padded to a multiple, masked

CONTROL_DTYPES = {"bfloat16": jnp.float8_e4m3fn}


def compare(items: list, gen, ref, *, control: str | None = None) -> dict:
    """items: (uid, ctx, out, woken).  Returns the readings."""
    by_uid = defaultdict(list)
    for it in items:
        by_uid[it[0]].append(it)
    errs, ctl, woken, nonfinite = [], [], 0, 0
    for uid, its in sorted(by_uid.items()):
        lpad = math.ceil(max(it[1] for it in its) / CTX_ROUND) * CTX_ROUND
        k, v = gen.session_kv(uid, lpad)
        for b in range(0, len(its), BLOCK):
            blk = its[b:b + BLOCK]
            pad = blk + [blk[0]] * (BLOCK - len(blk))
            q = gen.q([uid] * BLOCK, [it[1] - 1 for it in pad])
            lengths = jnp.asarray([it[1] for it in pad], jnp.int32)
            r = np.asarray(ref.attend(q, k, v, lengths))
            c = (np.asarray(ref.attend(q, k, v, lengths,
                                       kv_dtype=CONTROL_DTYPES[control]))
                 if control else None)
            for j, (_, _, out, was_woken) in enumerate(blk):
                o = np.asarray(out)
                scale = float(np.max(np.abs(r[j])))
                if not np.isfinite(o).all():
                    nonfinite += 1
                    errs.append(math.inf)
                else:
                    errs.append(float(np.max(np.abs(o - r[j]))) / scale)
                if c is not None:
                    ctl.append(float(np.max(np.abs(c[j] - r[j]))) / scale)
                woken += bool(was_woken)
    out = {"answers_checked": len(errs), "woken_answers_checked": woken,
           "nonfinite_answers": nonfinite,
           "attend_rel_err": max(errs) if errs else math.inf,
           "errs": errs}
    if control:
        out["control_rel_err"] = max(ctl) if ctl else math.inf
    return out


def judge(readings: dict, limits: dict) -> tuple[bool, dict]:
    """Each compared number beside its limit; correct only if all hold.
    A limit is {"max": x} or {"min": x}."""
    shown, ok = {}, True
    for name, lim in limits.items():
        val = readings.get(name)
        if "max" in lim:
            good = val is not None and val <= lim["max"]
            shown[name] = {"value": val, "limit": lim["max"], "holds": "<="}
        else:
            good = val is not None and val >= lim["min"]
            shown[name] = {"value": val, "limit": lim["min"], "holds": ">="}
        ok = ok and good
    return ok, shown
