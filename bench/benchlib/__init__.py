"""The LIDER serving benchmark's own machinery (see ``bench/run.py``).

Nothing here imports the program at import time: ``system`` loads
``src/repro`` when a run builds the system under test, so the tests of the
yardstick (``bench/tests``) run without it and without a chip.
"""
