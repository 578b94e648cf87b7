"""Adds the tiny sizes of cells that ``cellkit`` does not list itself."""

import lcekit

lcekit.register()
