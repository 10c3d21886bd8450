"""One driver per kind of traffic mix (the ``kind`` of its file)."""
