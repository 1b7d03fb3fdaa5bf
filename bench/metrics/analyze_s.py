"""analyze_s: mean wall of the ``analyze`` span (``core/queries``, ``plan``,
``temporal``, ``kernels``) per pass of the window."""


def read(obs):
    d = obs["spans"].get("analyze")
    return sum(d) / len(d) if d else None
