"""device_idle_share.sketch: the share of one traced pass of the
sketch-tier service and its snapshot in which no operation ran on the
device, in percent."""


def read(obs):
    t = obs.get("trace")
    if not t or t.get("idle_share") is None or t["busy_s"] <= 0:
        return None
    return 100.0 * t["idle_share"]
