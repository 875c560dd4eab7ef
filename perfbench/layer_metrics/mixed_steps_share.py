"""Steps of the window that ran the mixed step (a decode block beside a
prefill block) over all its steps, from the program's own counters
(``prefill_steps`` over ``decode_steps + prefill_steps``): which population
of steps the tail of the gaps between tokens is reading. Under a twentieth
``itl_p95_ms`` is a decode step; well over it, a mixed step."""


def read(rec):
    mixed = rec.counters.get("prefill_steps")
    decode = rec.counters.get("decode_steps")
    if mixed is None or decode is None or not mixed + decode:
        return None
    return 100.0 * mixed / (mixed + decode)
