"""Simulated rack-seconds conditioned per second of wall time: racks times
campus-seconds of every call in the window, over the window's wall time
(first issue to the last call's whole result)."""


def read(run):
    w = run.window
    rack_s = run.dep.n_racks * sum(run.call_seconds for _ in w.positions)
    return rack_s / w.wall
