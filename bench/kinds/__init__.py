"""Job kinds: one module each, a generator of jobs and their plain reference.

A kind module defines ``make(params, rng, own, shared) -> dict`` and
``answer(result) -> host value``; the harness loads it by the ``kind``
name in a traffic file.  Each :class:`Job` carries its expected answer,
worked out by the module's reference, which imports nothing of the
program under test.
"""
