"""Launchers: mesh construction, compile cache, distributed init, training
and serving CLIs."""
