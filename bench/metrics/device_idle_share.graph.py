"""device_idle_share.graph: the share of one traced pass of the
Graphalytics cell (read, build, anonymize and the graph phase) in which no
operation ran on the device, in percent; read as the batch cell's."""
from pathlib import Path

from bench.harness import load_module

read = load_module("metrics", "device_idle_share.batch",
                   Path(__file__).resolve().parent.parent).read
