"""Plain reference of the GreediRIS round and the query service.

Straight NumPy on the host, independent of the program under test: it
builds the graph tables from the edge list itself, draws the same
coins from the same keys (``prng``), walks each RRR set on its own, and
solves max-k-cover by a lazy heap.  What the timed path produces is
compared against it.
"""
