"""update_dev_s: device seconds per step of the engine's trailing and
panel updates, ``bench.factor/repro.TU`` + ``bench.factor/repro.PU``.

Read from the phase and kernel split of :mod:`bench.phases`.
"""


def read(summary):
    return summary.split.per_step()["update_dev_s"]
