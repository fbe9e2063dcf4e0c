"""Plain references: ``ctr`` is the training pass, ``models/<name>.py`` one
dense net each (named by a configuration's ``reference`` key)."""
