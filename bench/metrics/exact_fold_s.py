"""exact_fold_s: mean wall of one micro-batch's exact-tier fold
(``stream/engine.update_state``), dispatch to device sync, under
``time_phases``."""


def read(obs):
    d = obs["spans"].get("fold.exact")
    return sum(d) / len(d) if d else None
