"""Launchers: the train and serve drivers, and the compile cache they share."""
