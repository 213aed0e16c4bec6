"""Deployment builders, one per kind of configuration, named by the
configuration file's ``builder`` key.  ``build(config, seed)`` returns a
``deploy.Deployment``."""
