"""wcc_step_s: wall of one step of the wcc fixed point: the window's
``challenge/graph/wcc`` span walls (dispatch to device sync) summed, over
the steps its ``graph.wcc.iterations`` counters sum to."""


def read(obs):
    walls = obs["spans"].get("graph/wcc")
    steps = obs.get("iterations", {}).get("wcc")
    if not walls or not steps or len(walls) != len(steps) or sum(steps) <= 0:
        return None
    return sum(walls) / sum(steps)
