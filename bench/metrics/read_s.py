"""read_s: mean wall of the batch pipeline's ``read`` span (host plq
decode, ``data/plq.py``) per pass of the window."""


def read(obs):
    d = obs["spans"].get("read")
    return sum(d) / len(d) if d else None
