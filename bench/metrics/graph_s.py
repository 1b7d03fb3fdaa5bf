"""graph_s: mean wall of the ``graph`` span per pass of the window: the
graph phase of ``run_challenge`` (the plan pair and CSR pair, then each
selected kernel's program, dispatch to device sync)."""


def read(obs):
    d = obs["spans"].get("graph")
    return sum(d) / len(d) if d else None
