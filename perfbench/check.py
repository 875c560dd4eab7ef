"""The comparison that decides ``correct``.

After the window has closed, a seeded sample of the requests it finished
(the longest among them) is read once by the plain float32 reference: the
prompt followed by the tokens the program served. At every served position
the reference gives a row of logits; a sound greedy server's token is the
reference's best or lies just below it. The number compared at a position
is that gap in units of the row's own spread:

    gap = (best reference logit - reference logit of the served token)
          / standard deviation of the reference's row

Two numbers are held to a limit each: the widest gap (``gap_max``) and the
mean gap (``gap_mean``) over all served positions of the sample. Valid for
greedy tokens only, which is what every mix sends. The same two numbers of
each request alone stand beside the pooled ones (``per_request``).

The control is the reference itself in the precision below the
configuration's (float8 for bfloat16), put in the program's place: at each
position, the gap of the token that the lower precision puts first.
"""

from __future__ import annotations

# Limits by served dtype, for a cell whose file states none. They were read
# on the chip at the one-chip configuration's sizes (PERF.md section 2 gives
# the readings); a cell of another model states its own in
# ``cells/<cell>.json``, set from readings at its own sizes.
DEFAULT_LIMITS = {"bfloat16": {"gap_max": 0.2, "gap_mean": 0.004}}


def gaps(read) -> "list[float]":
    return ((read["best"] - read["picked"]) / read["std"]).tolist()


def summarise(all_gaps) -> dict:
    return {"gap_max": max(all_gaps), "gap_mean": sum(all_gaps) / len(all_gaps),
            "top1_share": sum(1 for g in all_gaps if g <= 0) / len(all_gaps)}


CONTROL_PRECISION = "fp8"


def compare(family, sizes, seed: int, sample, device, *, limits=None,
            control: bool = False) -> dict:
    """``sample`` is a list of (prompt, served tokens); ``family`` is the
    configuration's (``perfbench/families/``). Returns the numbers compared
    beside their limits, and ``correct``."""
    import zlib

    import numpy as np

    from perfbench import reference, weights

    limits = {**DEFAULT_LIMITS.get(sizes.dtype, {}), **(limits or {})}
    if not limits:
        raise ValueError(f"no limits for {sizes.dtype}: the cell's file "
                         f"has to state them")
    out = {"limits": limits, "requests": len(sample),
           "tokens": sum(len(o) for _, o in sample), "correct": False}
    if not sample:
        out["why"] = "no finished request to compare"
        return out
    w = weights.Weights(family, sizes, seed, device)
    seqs = [(list(prompt) + list(served), len(prompt))
            for prompt, served in sample]
    sound = [gaps(r) for r in reference.forward_positions(w, seqs)]
    # One row a request, in the sample's order: what that request reads on
    # its own. A sample can be one wide request and little else, so a limit
    # stands above the largest of these (limits.py reads them all).
    out["per_request"] = [
        {"prompt_crc32": zlib.crc32(np.asarray(prompt, np.int32).tobytes()),
         "prompt_tokens": len(prompt), "tokens": len(served), **summarise(g)}
        for (prompt, served), g in zip(sample, sound)]
    out["compared"] = summarise([g for one in sound for g in one])
    out["correct"] = all(out["compared"][k] <= limits[k] for k in limits)
    if control:
        low = reference.forward_positions(w, seqs,
                                          precision=CONTROL_PRECISION)
        ref_low = reference.forward_positions(
            w, seqs, gather=[r["best_token"] for r in low])
        ctrl = [gaps(r) for r in ref_low]
        for row, g in zip(out["per_request"], ctrl):
            row["control"] = summarise(g)
        out["control"] = summarise([g for one in ctrl for g in one])
        out["control_correct"] = all(out["control"][k] <= limits[k]
                                     for k in limits)
    return out
