"""The plain reference of the conditioner.  It imports nothing of the
program and takes nothing the program made: it derives its constants from
the configuration and renders its own traces from the deployment's data."""
