"""The benchmark's plain reference: a frozen copy of the program's CPU
oracle (``cilium_tpu/policy/oracle.py``) and of what it needs to turn
CNP documents and endpoint labels into per-identity map states
(``core/flow``, ``core/identity``, ``core/labels``, ``policy/api``,
``policy/mapstate``, ``policy/repository``, ``policy/selectorcache``,
``policy/compiler/matchpattern``, ``secrets``), copied at PR 21's
commit with imports made relative. It imports nothing of
``cilium_tpu``, so a later PR that changes the program cannot move it.

Edits against the copy: ``Rule.sanitize``, the oracle's record entry
points, ``verdict_one`` and the proxy-action helpers are left out (the
benchmark calls none of them), the rule regexes are compiled once,
and ``OracleVerdictEngine(l7_enforced=False)`` is the control of
``benchmark/compare.py``.
"""
