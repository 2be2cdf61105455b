"""The on-chip benchmark's harness: everything that is the same for every
cell.  What belongs to one configuration, traffic mix or metric lives in
its own data file or reader under ``bench/`` and is found by name."""
