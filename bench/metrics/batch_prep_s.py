"""batch_prep_s: mean host wall of one micro-batch before its fold in the
exact-tier service: the row group read through ``Prefetcher``, cast and
pad, and ``device_put`` to a device sync, under ``time_phases``."""


def read(obs):
    d = obs["spans"].get("batch_prep")
    return sum(d) / len(d) if d else None
