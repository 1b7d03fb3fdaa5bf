"""The chip benchmark of the network-sensing challenge (see ``run.py``).

Everything a cell needs is found by name under this directory:
``workloads/<cell>.json`` names the configuration, the traffic mix and the
window driver; ``configs/<config>.json`` holds the deployment's sizes and
guarantees; ``traffic/<mix>.json`` the generator's parameters;
``drivers/<driver>.py`` runs the window; ``metrics/<metric>.py`` reads one
per-layer metric.  The yardstick (traffic generation, the NumPy reference,
the trace reduction and the peak table) lives here too, apart from the
program under test in ``src/``.
"""
