"""Launchers: production mesh, multi-pod dry-run, train/serve CLIs, and
the scenario-driven serving benchmark (``bench_serving``).

Importing any of them changes no environment: ``dryrun``, ``hillclimb``
and ``profile_tpu`` set ``XLA_FLAGS`` (512 placeholder host devices) in
their ``main()``, before JAX initializes a backend.
"""
