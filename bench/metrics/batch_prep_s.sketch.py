"""batch_prep_s.sketch: ``batch_prep_s`` in the sketch-tier service: mean
host wall of one micro-batch before its fold (row group read through
``Prefetcher``, cast and pad, ``device_put`` to a device sync), under
``time_phases``."""


def read(obs):
    d = obs["spans"].get("batch_prep")
    return sum(d) / len(d) if d else None
