"""On-chip benchmark of the consensus trainer (see ``run.py``).

Everything that measures lives here: traffic generation, the seeded
weights, the plain references, the comparison that decides ``correct``,
the trace reduction, FLOP and byte counts and the table of peaks. From the
program it takes only the launcher it drives and the kernel names in the
device trace.
"""
