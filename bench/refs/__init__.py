"""Plain references, one module per semantics, found by the name a
configuration gives under ``"reference"``."""
