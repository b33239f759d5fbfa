"""Share of the window's iterations that ran inside a fused loop
executable: the change of the executor's ``loop_iterations`` counter over
the iterations the window completed."""


def read(w):
    n = w.rec.counters.get("loop_iterations")
    if n is None or not w.measured.units:
        return None
    return 100.0 * n / w.measured.units
