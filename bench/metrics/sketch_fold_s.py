"""sketch_fold_s: mean wall of one micro-batch's sketch-tier fold
(``core/sketch.update_sketch`` and its Pallas kernels), dispatch to device
sync, under ``time_phases``."""


def read(obs):
    d = obs["spans"].get("fold.sketch")
    return sum(d) / len(d) if d else None
