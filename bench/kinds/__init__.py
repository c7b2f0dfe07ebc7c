"""One driver per kind of configuration (``"kind"`` in its file)."""
