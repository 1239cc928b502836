"""Configuration kinds: the shape of a deployment, as one module each.

``configs/<name>.json`` may carry ``"kind"``; the harness finds
``kinds/<kind>.py`` (or a package of that name) by it
(``deployment.kind_of``) and asks it for everything that depends on the
deployment's shape. A kind gives:

``load(cfg)``                 the configuration with the keys this kind
                              requires checked (raises ``ValueError``)
``schedule(cfg, seed)``       the arrivals as plain data, sorted by due
                              time. The harness reads ``key``
                              (``<namespace>/<name>``, the store's key of
                              the workload), ``klass``, ``runtime_s`` and
                              ``due_s`` of an arrival; a kind may add
                              fields of its own (a podset count, a
                              topology request)
``top_class(cfg)``            the ``klass`` whose wait is the tail metric
``scaled(cfg, cohorts, cqs_per_cohort, count_div)``
                              a smaller copy for ``--rehearse`` and the
                              tests (a kind reads the three as its own
                              shape allows); no measurement calls it
``build_store(cfg)``          the program's ``Store`` holding the
                              deployment, no workloads
``make_workload(arrival, cfg)`` the program's ``Workload`` of an arrival;
                              these two are the only places that import
                              the program's API types
``scheduler_options(cfg)``    keyword arguments for ``Scheduler(...)``
                              that are deployment settings of upstream's
                              ``Configuration`` API; the harness refuses
                              any key outside
                              ``driver.SCHEDULER_OPTIONS``, so that no kind
                              sets a router threshold
``feature_gates(cfg)``        optional: upstream's ``featureGates`` of the
                              deployment, name -> bool. The harness
                              refuses any name outside
                              ``driver.FEATURE_GATES`` (the gates of
                              upstream's ``kube_features.go`` that change
                              what the scheduler decides), the program's
                              own gates among them, and sets them once,
                              before anything of the program is built.
                              A kind without it sets none
``GIVEN``                     optional: ``True`` asks for ``given`` in
                              every pass-log record (below): a kind
                              whose guarantees are about WHICH quota and
                              WHICH nodes
``audit(cfg, arrivals, preloaded, pass_log)``
                              the kind's plain reference. It imports
                              nothing of the program and returns
                              ``counts`` (name -> count, each compared
                              with the limit 0; the names are the
                              kind's), ``first`` (name -> the first
                              breach), ``holding`` and ``finished``
``controls``                  name -> (``deploy(cfg)``: the deployment
                              the program is given, one that breaks a
                              stated guarantee; the count that then has
                              to read above 0). The reference is always
                              given the configuration as stated

A pass-log record holds ``events`` [(``arrive`` | ``finish``, key, due_s)],
``added`` and ``removed`` (keys that gained or lost a quota reservation
between the end of the pass before and the end of this one): a workload
that loses and regains its reservation inside one pass stands in neither.
For a kind with ``GIVEN`` a record also holds ``given``: one entry for
EVERY reservation the store reported in the pass, in order, such a
workload's among them, as plain data (``driver.given_as_data``):
``{"key", "podsets": [{"name", "count", "flavors": resource -> flavor
name, "usage": resource -> quantity, "topology": {"levels", "domains":
[[values, count], ...]} or None}, ...]}``. A record of a kind that does
not ask has no ``given``.
"""
