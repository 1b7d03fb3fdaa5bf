"""bfs_step_s: wall of one step of the bfs fixed point: the window's
``challenge/graph/bfs`` span walls (dispatch to device sync) summed, over
the steps its ``graph.bfs.iterations`` counters sum to."""


def read(obs):
    walls = obs["spans"].get("graph/bfs")
    steps = obs.get("iterations", {}).get("bfs")
    if not walls or not steps or len(walls) != len(steps) or sum(steps) <= 0:
        return None
    return sum(walls) / sum(steps)
