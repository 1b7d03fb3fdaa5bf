"""build_s: mean wall per pass of ``build_host`` (window ids and padding on
the host) plus ``build_device`` (transfer and the traffic-matrix group-by)."""


def read(obs):
    host, dev = obs["spans"].get("build_host"), obs["spans"].get("build_device")
    if not host or not dev or len(host) != len(dev):
        return None
    return sum(h + d for h, d in zip(host, dev)) / len(host)
