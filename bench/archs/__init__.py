"""Work counts of each architecture a configuration can name."""
