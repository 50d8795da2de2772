"""The benchmark's yardstick and harness.

Modules that the program under test can never change the meaning of:
``pool`` (queries and answers, the paper's Eq. 1 error model), ``traffic``
(one general generator over the data files in ``traffic/``), ``reference``
(the plain per-request router), ``planref`` (the plain planner), ``fold``
(the plain feedback loop: the fold of returned labels and the drift gate),
``check`` (the comparison that decides ``correct``), ``stats`` (latency and
rate arithmetic), ``trace`` (profiler trace to busy time, idle share and
gaps), ``peaks`` and ``work``.
``harness`` drives the program; it is the only module that imports it.
"""
