"""Of the views that the program's XLA blocks read or wrote and that are
neither a whole base nor one slice of it, the share in % lowered to a
transpose or broadcast of one slice (``permutes``) and not to a static
index gather (``gathers``): the args of its ``block`` spans over the
traced window.  A program whose ``block`` spans lack the args, or whose
blocks take no such view, reads nothing.  Reads
``view.permute_share.<cell family>``."""


def read(w):
    permutes = gathers = 0
    for ev in w.rec.spans:
        args = ev.get("args", {})
        if ev.get("ph") == "X" and ev["name"] == "block" and "gathers" in args:
            permutes += args["permutes"]
            gathers += args["gathers"]
    if not permutes + gathers:
        return None
    return 100.0 * permutes / (permutes + gathers)
