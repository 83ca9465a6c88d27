"""Mean per cent of the decode slots in use over the window's decode waves
(a count the engine's scheduler holds, read before each wave)."""


def read(rec):
    if "ticks" not in rec:
        return None
    t0, t1 = rec["window"]
    occ = [d for kind, a, b, d, _ in rec["ticks"]
           if kind == "work" and a >= t0 and b <= t1]
    if not occ:
        return None
    return 100.0 * sum(occ) / len(occ) / rec["max_slots"]
