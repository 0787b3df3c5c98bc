"""mamba_share.hybrid (%): device time under the Mamba-2 layers' scopes
(``mamba.mixer`` and what it holds, models/mamba2.py), over busy time, each
operation read in the program that ran it (bench/lib/programs.py). Nothing
to read (None) where no traced operation carries a program scope."""
from bench.lib import programs

MAMBA = r"mamba\.[a-z_]+"


def read(view):
    return programs.busy_share(view, inside=(MAMBA,))
