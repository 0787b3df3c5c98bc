"""moe_share.hybrid (%): device time under the expert layers' scopes
(``moe.route``, ``moe.experts``, ``moe.shared``, models/moe.py), over busy
time, each operation read in the program that ran it
(bench/lib/programs.py). Nothing to read (None) where no traced operation
carries a program scope."""
from bench.lib import programs

MOE = r"moe\.[a-z_]+"


def read(view):
    return programs.busy_share(view, inside=(MOE,))
