"""One driver per kind of configuration: set-up, window and check."""
