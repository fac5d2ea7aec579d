"""The benchmark's yardstick: everything a later PR may not change.

``spec``       BENCHMARK.json and the files its names resolve to
``job``        the measured window (closed loop of steps), watchdog
``checks``     what decides ``correct``: plain reference step, tolerances
``plain``      float32 jax.numpy building blocks of the plain references
``intervals``  union / exposed-time arithmetic on [start, end) intervals
``xplane``     .xplane.pb -> device ops, modules, host annotations
``flops``      operations and bytes from shapes
``peaks``      published peaks keyed by exact ``device_kind``
"""
