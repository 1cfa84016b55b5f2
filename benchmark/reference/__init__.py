"""Plain reference implementations: what decides ``correct``."""
