"""Request kinds, one module each, found by the name in a mix's ``kind``.

A kind states ``PATH``, ``body(spec)``, ``distinct(spec, name)``,
``expected(reference, spec)``, ``verdict(response)`` and
``gave_up(verdict)``: see ``sar.py`` and benchmark/README.md.
"""
