"""Operation and byte counts the served model's algorithm needs, from
its own shapes and kept tiles and from the traffic: the numerators of
the rooflines and of ``mfu``. They count what the work requires, never
what an implementation happens to do, so a share reads the same work
after a kernel is replaced.

The model module's ``structure(artifact, block)`` (``bench/models``)
reads the served model's shapes once at set-up into plain numbers that
the functions here take. A kernel's module here also declares
``TRACE``, the pattern by which ``bench/devtrace.py`` finds the
kernel's events in the device trace.
"""
