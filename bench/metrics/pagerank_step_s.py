"""pagerank_step_s: wall of one step of the pagerank fixed point: the window's
``challenge/graph/pagerank`` span walls (dispatch to device sync) summed, over
the steps its ``graph.pagerank.iterations`` counters sum to."""


def read(obs):
    walls = obs["spans"].get("graph/pagerank")
    steps = obs.get("iterations", {}).get("pagerank")
    if not walls or not steps or len(walls) != len(steps) or sum(steps) <= 0:
        return None
    return sum(walls) / sum(steps)
