"""The benchmark's plain reference: a from-scratch oracle of the cut-point
search's cost model.

``oracle.Oracle`` prices one cut tuple at a time: the reuse policy of the
tuple (``blocks.py``), the allocator of Algorithm 1 over the whole graph
(``allocator.py``), and the SRAM (eqs. 1-7), DRAM (eqs. 8-9) and latency
reports (``sram.py``, ``dram.py``, ``timing.py``), whose latency total is
added group by group, left to right.  The graph comes from this package's
own network definitions and grouping (``zoo.py``, ``ir.py``,
``grouping.py``).

These modules were copied from the compiler's scalar oracle
(``repro/core`` and ``repro/cnn/zoo.py``) and cut to that path: none of
the compiler's incremental engine, tables, batched reductions, device
replay or options is here, and nothing imports the program or jax, so an
error in those reaches the program alone.  ``zoo.py`` keeps every network
of the compiler's zoo, so a configuration of another network is a new
configuration file only.
"""
