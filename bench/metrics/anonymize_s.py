"""anonymize_s: mean wall of the ``anonymize`` span (``core/anonymize.py``)
per pass of the window."""


def read(obs):
    d = obs["spans"].get("anonymize")
    return sum(d) / len(d) if d else None
