"""One file an entry kind (a traffic file's ``entry`` key): ``build(ctx)``
returns an object with the calls ``harness.py`` drives. What the harness
may touch of the program is here and nowhere else."""
