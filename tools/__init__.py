"""Operational tools: the lint driver, the sanitizer matrices, trace and
job viewers.

Importable as a package (tests import them); each tool also runs
standalone (``python tools/<name>.py``).
"""
