"""The benchmark's own code: everything that measures, and nothing measured.

``manifest`` finds a cell, its configuration, its traffic mix and its
metric readers by name; ``stencil`` builds the operators and holds the
plain reference; ``workbytes`` and ``peaks`` are the yardstick of the
roofline shares; ``tracing`` reduces a profiler trace to busy, idle and
collective time; ``runner`` drives one run of one cell.
"""
